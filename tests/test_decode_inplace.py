"""The decode step's in-place cache write.

Groups of full-attention layers carry their stacked KV cache through the
layer scan and write each slot's new row at ``[layer, slot, t_slot]``;
every other group, and every sequence-sharded plan, keeps the scan that
takes each layer's cache in as input and gives it back as output.  The
two paths write the same rows and attend the same values, so their
logits and caches must be equal bit for bit, step after step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.configs.reduced import reduced_config
from repro.models import build_model, lm
from repro.plan import LaunchPlan, plan_scope

B, MAX_LEN, STEPS = 3, 48, 3
T0 = (5, 17, 40)                      # a different position per slot


def _gqa(heads: int, kv_heads: int, **kw) -> ModelConfig:
    """A dense GQA decoder at tiny widths, with the group of a served
    model (16 / 2 as qwen2.5-3b, 12 / 2 as qwen2.5-1.5b)."""
    return ModelConfig(
        name=f"gqa{heads}x{kv_heads}", family="dense", num_layers=3,
        d_model=64, num_heads=heads, num_kv_heads=kv_heads, d_ff=128,
        vocab_size=128, head_dim=16, qkv_bias=True, rope_theta=1e4,
        tie_embeddings=True, **kw)


def _filled_cache(model, kv_dtype: str, key):
    """The cache as a prefill would leave it: every row holds values."""
    cache = model.init_cache(B, MAX_LEN, kv_dtype)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, a in zip(keys, leaves):
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 128, jnp.int8))
        elif a.ndim == 4:            # (layers, B, L, H) quantization scales
            out.append(jax.random.uniform(k, a.shape, a.dtype, 0.001, 0.02))
        else:
            out.append(jax.random.normal(k, a.shape, a.dtype))
    return jax.tree.unflatten(tree, out)


def _run(cfg, params, cache, steps=STEPS):
    """Greedy decode ``steps`` tokens; -> [(logits, cache)] per step."""
    step = jax.jit(lambda p, c, tok, t: lm.lm_decode_step(p, cfg, c, tok, t))
    tok = jnp.arange(B, dtype=jnp.int32) + 1
    t = jnp.asarray(T0, jnp.int32)
    outs = []
    for _ in range(steps):
        logits, cache = step(params, cache, tok, t)
        outs.append((logits, cache))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = t + 1
    return outs


@pytest.mark.parametrize("heads,kv_heads,impl,kv_dtype,scan_layers", [
    (16, 2, "xla", "bfloat16", True),
    (16, 2, "pallas", "bfloat16", True),
    (12, 2, "xla", "bfloat16", True),
    (12, 2, "pallas", "bfloat16", True),
    (16, 2, "pallas", "int8", True),
    (12, 2, "xla", "bfloat16", False),
], ids=["group8-xla", "group8-pallas", "group6-xla", "group6-pallas",
        "group8-pallas-int8", "group6-xla-unscanned"])
def test_in_place_step_matches_scan(monkeypatch, heads, kv_heads, impl,
                                    kv_dtype, scan_layers):
    cfg = _gqa(heads, kv_heads, attention_impl=impl,
               scan_layers=scan_layers)
    assert lm.decode_cache_update(cfg) == "in_place"
    assert lm.decode_inplace_layers(cfg) == cfg.num_layers
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = _filled_cache(model, kv_dtype, jax.random.PRNGKey(1))

    in_place = _run(cfg, params, cache)
    monkeypatch.setattr(lm, "_writes_in_place", lambda *a, **k: False)
    assert lm.decode_cache_update(cfg) == "scan"
    scanned = _run(cfg, params, cache)

    for i, ((la, ca), (lb, cb)) in enumerate(zip(in_place, scanned)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=f"logits, step {i}")
        for path, a in jax.tree_util.tree_leaves_with_path(ca):
            b = dict(jax.tree_util.tree_leaves_with_path(cb))[path]
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"cache leaf {jax.tree_util.keystr(path)}, "
                        f"step {i}")
    # the rows written are the new tokens', at each slot's position
    _, last = in_place[-1]
    k = last[0][0]["k"]
    for b, t0 in enumerate(T0):
        assert not np.array_equal(np.asarray(k[:, b, t0]),
                                  np.asarray(cache[0][0]["k"][:, b, t0]))


def _seq_shard_plan() -> LaunchPlan:
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    return LaunchPlan(kind="decode", seq_shard_mesh=mesh)


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "minicpm3-4b",
                                  "mamba2-780m"],
                         ids=["windowed", "mla", "ssm"])
def test_other_layer_kinds_keep_the_scan(arch):
    cfg = reduced_config(arch)
    assert lm.decode_cache_update(cfg) == "scan"
    assert lm.decode_inplace_layers(cfg) == 0


def test_sequence_sharded_plans_keep_the_scan():
    cfg = _gqa(16, 2)
    plan = _seq_shard_plan()
    assert lm.decode_cache_update(cfg, plan) == "scan"
    with plan_scope(plan):
        assert lm.decode_cache_update(cfg) == "scan"
        assert lm.decode_inplace_layers(cfg) == 0
    context_only = dataclasses.replace(plan, seq_shard_mesh=None)
    assert lm.decode_cache_update(cfg, context_only) == "in_place"
