"""AOT compiles of the Pallas kernels for a TPU v5e that is described,
not attached.

Each test lowers one kernel at qwen2.5-3b widths (H_Q=16, H_KV=2,
D=128; decode B=4 over a 4096-row cache, prefill a 512-token prompt)
with ``interpret=False`` and compiles it for one chip of a ``v5e:2x2``
topology description.  The v5e compiler refuses block shapes Mosaic
cannot tile, which interpret mode never checks; a compile that passes
must also hold the Mosaic kernel (``tpu_custom_call``), i.e. nothing
fell back to plain XLA.  Nothing runs, so these say nothing about
results or times — ``tests/test_kernels.py`` checks the numerics.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.scheduler_metadata import get_scheduler_metadata
from repro.kernels import ops
from repro.kernels.flash_combine import flash_combine
from repro.kernels.flash_decode import (flash_decode_partials,
                                        flash_decode_quant_partials)
from repro.kernels.flash_prefill import flash_prefill

B, HQ, HKV, D, L = 4, 16, 2, 128, 4096      # qwen2.5-3b decode
G = HQ // HKV
PROMPT = 512                                 # prefill bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_v5e(topo):
    """-> ``compile_(fn, *(shape, dtype))``: the compiled HLO text of
    ``jax.jit(fn)`` for one v5e chip.  The persistent compilation cache
    is off meanwhile: an entry written for a described chip cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    chip = SingleDeviceSharding(topo.devices[0])

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("num_splits", [1, 4])
def test_flash_decode_partials_compiles(compile_v5e, num_splits):
    def fn(q, k, v, kv_len):
        return flash_decode_partials(q, k, v, kv_len, num_splits=num_splits,
                                     interpret=False)

    text = compile_v5e(fn, ((B, HKV, G, D), jnp.bfloat16),
                       ((B, L, HKV, D), jnp.bfloat16),
                       ((B, L, HKV, D), jnp.bfloat16), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("storage", [jnp.int8, jnp.float8_e4m3fn])
def test_flash_decode_quant_partials_compiles(compile_v5e, storage):
    def fn(q, k, v, ks, vs, kv_len):
        return flash_decode_quant_partials(q, k, v, ks, vs, kv_len,
                                           num_splits=4, interpret=False)

    text = compile_v5e(fn, ((B, HKV, G, D), jnp.bfloat16),
                       ((B, L, HKV, D), storage), ((B, L, HKV, D), storage),
                       ((B, L, HKV), jnp.float32), ((B, L, HKV), jnp.float32),
                       ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_prefill_compiles(compile_v5e):
    def fn(q, k, v):
        return flash_prefill(q, k, v, causal=True, interpret=False)

    text = compile_v5e(fn, ((1, PROMPT, HQ, D), jnp.bfloat16),
                       ((1, PROMPT, HKV, D), jnp.bfloat16),
                       ((1, PROMPT, HKV, D), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_combine_compiles(compile_v5e):
    S = 4

    def fn(acc, l, m):
        return flash_combine(acc, l, m, interpret=False)

    text = compile_v5e(fn, ((S, B, HKV, G, D), jnp.float32),
                       ((S, B, HKV, G), jnp.float32),
                       ((S, B, HKV, G), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_attention_pallas_compiles(compile_v5e, quantized):
    """The dispatch the serving decode step runs: GQA-pack, pad, split
    kernel, combine kernel — both kernels must be in the program."""
    plan = get_scheduler_metadata(B, 1, L, HQ, HKV, D, num_splits_override=4)

    if quantized:
        def fn(q, k, v, ks, vs, kv_len):
            return ops.decode_attention(q, k, v, kv_len, k_scale=ks,
                                        v_scale=vs, plan=plan,
                                        impl="pallas", interpret=False)
        shapes = (((B, HQ, D), jnp.bfloat16), ((B, L, HKV, D), jnp.int8),
                  ((B, L, HKV, D), jnp.int8), ((B, L, HKV), jnp.float32),
                  ((B, L, HKV), jnp.float32), ((B,), jnp.int32))
    else:
        def fn(q, k, v, kv_len):
            return ops.decode_attention(q, k, v, kv_len, plan=plan,
                                        impl="pallas", interpret=False)
        shapes = (((B, HQ, D), jnp.bfloat16), ((B, L, HKV, D), jnp.bfloat16),
                  ((B, L, HKV, D), jnp.bfloat16), ((B,), jnp.int32))

    text = compile_v5e(fn, *shapes)
    assert text.count("tpu_custom_call") >= 2, "split + combine kernels"


def test_prefill_attention_pallas_compiles_on_a_mesh(topo, compile_v5e):
    """The prefill attention of a sequence-sharded engine (a 1 x 4 mesh
    bound by ``activation_mesh``): GSPMD cannot partition a Mosaic
    kernel, so each chip must run it on the whole prompt."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.sharding.ctx import activation_mesh

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))

    def fn(q, k, v):
        with activation_mesh(mesh):
            return ops.attention(q, k, v, causal=True, impl="pallas",
                                 interpret=False)

    rep = NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=rep)
            for s in ((1, PROMPT, HQ, D), (1, PROMPT, HKV, D),
                      (1, PROMPT, HKV, D))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_decode_step_writes_the_cache_in_place(topo, compile_v5e,
                                               monkeypatch):
    """The whole decode step at qwen2.5-3b widths (8 layers), the cache
    donated as the serving engine donates it: the layer scan writes the
    new rows into the stacked cache in place, so no whole-cache copy is
    left in the program and its temporaries stay under two layers' K+V
    (the scan that rebuilt the cache through its outputs needed more
    than the whole stack)."""
    import dataclasses
    import re
    from repro.configs.base import ModelConfig
    from repro.kernels import flash_combine, flash_decode
    from repro.models import build_model

    # the step asks for interpret=None, which the CPU backend resolves to
    # the interpreter: compile the Mosaic kernels for the described chip
    for mod in (flash_decode, flash_combine):
        monkeypatch.setattr(mod, "resolve_interpret", lambda i=None: False)
    layers = 8
    cfg = ModelConfig(name="qwen2.5-3b-8l", family="dense",
                      num_layers=layers, d_model=2048, num_heads=HQ,
                      num_kv_heads=HKV, d_ff=11008, vocab_size=151936,
                      head_dim=D, qkv_bias=True, rope_theta=1e6,
                      tie_embeddings=True, attention_impl="pallas")
    model = build_model(cfg)
    plan = dataclasses.replace(
        get_scheduler_metadata(B, 1, L, HQ, HKV, D), impl="pallas")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    def step(params, caches, token, t):
        return model.decode_step(params, caches, token, t, plan=plan)

    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        shaped(model.abstract_params()),
        shaped(model.abstract_cache(B, L)), vec, vec).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text

    layer_kv = 2 * B * L * HKV * D * 2           # K + V of one layer, bf16
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * layer_kv, (temp, layer_kv)
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    stack = f"bf16[{layers},{B},{L},{HKV},{D}]"
    copies = [ln for ln in entry.splitlines()
              if re.search(r"\scopy(-start)?\(", ln) and stack in ln]
    assert not copies, copies
