"""Pallas kernels vs the pure-jnp oracles, run by the Pallas interpreter
(the CPU backend's ``resolve_interpret`` default).

Shape/dtype sweeps per the assignment: every kernel asserts allclose
against ref.py on a grid of (batch, heads, lengths, dims, splits).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, strategies as st

from repro.kernels import ops, ref, resolve_interpret
from repro.kernels.flash_decode import flash_decode_partials
from repro.kernels.flash_prefill import flash_prefill


def _rand(rng, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# interpret-mode resolution: the interpreter only ever runs on the CPU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_resolve_interpret_follows_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret() is interpret
    assert resolve_interpret(None) is interpret
    assert resolve_interpret(False) is False     # AOT compiles for a TPU


def test_resolve_interpret_refuses_interpreter_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="emulate"):
        resolve_interpret(True)


# ---------------------------------------------------------------------------
# split_decode_xla: the oracle's own invariance (schedule != math)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(
    b=st.integers(1, 3),
    hkv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 4, 8]),
    lk=st.integers(2, 300),
    s=st.integers(1, 16),
    d=st.sampled_from([16, 64]),
)
def test_split_decode_invariant_to_split_count(b, hkv, g, lk, s, d):
    rng = jax.random.PRNGKey(lk * 131 + s)
    ks = jax.random.split(rng, 4)
    q = _rand(ks[0], (b, hkv * g, d))
    k = _rand(ks[1], (b, lk, hkv, d))
    v = _rand(ks[2], (b, lk, hkv, d))
    kv_len = jax.random.randint(ks[3], (b,), 1, lk + 1)
    want = ref.naive_decode_attention(q, k, v, kv_len)
    got = ref.split_decode_xla(q, k, v, kv_len, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_split_decode_mla_shapes():
    """Dv != Dqk (absorbed MLA latent attention)."""
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 3)
    q = _rand(ks[0], (2, 8, 40))          # latent+rope width 40
    k = _rand(ks[1], (2, 64, 1, 40))
    v = k[..., :32]                       # v = latent slice
    kv_len = jnp.array([64, 10], jnp.int32)
    want = ref.naive_decode_attention(q, k, v, kv_len)
    got = ref.split_decode_xla(q, k, v, kv_len, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas flash decode kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hkv,g,lk,s", [
    (1, 1, 8, 128, 1),
    (1, 1, 8, 512, 3),        # the paper's target shape (B=1, MQA, L=512)
    (1, 2, 4, 512, 3),        # H_KV=2 row of Table 1
    (2, 2, 2, 384, 1),
    (1, 1, 4, 1024, 4),
    (2, 4, 1, 256, 2),        # MHA-style (g=1)
    (1, 1, 1, 2048, 8),
])
def test_flash_decode_kernel_vs_oracle(b, hkv, g, lk, s, dtype):
    rng = jax.random.PRNGKey(b * 7 + lk)
    ks = jax.random.split(rng, 4)
    d = 128
    hq = hkv * g
    q = _rand(ks[0], (b, hq, d), dtype)
    k = _rand(ks[1], (b, lk, hkv, d), dtype)
    v = _rand(ks[2], (b, lk, hkv, d), dtype)
    kv_len = jax.random.randint(ks[3], (b,), 1, lk + 1)

    got = ops.decode_attention(
        q, k, v, kv_len, impl="pallas",
        metadata=__import__("repro.core.scheduler_metadata",
                            fromlist=["get_scheduler_metadata"]
                            ).get_scheduler_metadata(
            b, 1, lk, hq, hkv, d, num_splits_override=s))
    want = ref.naive_decode_attention(q, k, v, kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_flash_decode_split_determinism():
    """TPU combine is a deterministic reduction: same split -> same bits."""
    rng = jax.random.PRNGKey(3)
    ks = jax.random.split(rng, 4)
    q = _rand(ks[0], (1, 8, 128), jnp.bfloat16)
    k = _rand(ks[1], (1, 512, 1, 128), jnp.bfloat16)
    v = _rand(ks[2], (1, 512, 1, 128), jnp.bfloat16)
    kv_len = jnp.array([512], jnp.int32)
    md = __import__("repro.core.scheduler_metadata",
                    fromlist=["get_scheduler_metadata"]
                    ).get_scheduler_metadata(1, 1, 512, 8, 1, 128,
                                             num_splits_override=3)
    a = ops.decode_attention(q, k, v, kv_len, impl="pallas", metadata=md)
    b = ops.decode_attention(q, k, v, kv_len, impl="pallas", metadata=md)
    assert (np.asarray(a) == np.asarray(b)).all()


def test_flash_decode_partials_lse_combine_algebra():
    """Partials from the kernel + ref.lse_combine == unsplit softmax."""
    rng = jax.random.PRNGKey(11)
    ks = jax.random.split(rng, 3)
    B, Hkv, G, D, L, S = 2, 2, 4, 128, 512, 4
    q = _rand(ks[0], (B, Hkv, G, D)) * D ** -0.5
    k = _rand(ks[1], (B, L, Hkv, D))
    v = _rand(ks[2], (B, L, Hkv, D))
    kv_len = jnp.array([512, 300], jnp.int32)
    acc, l, m = flash_decode_partials(q, k, v, kv_len, num_splits=S)
    out = ref.lse_combine(acc, l, m).reshape(B, Hkv * G, D)
    want = ref.naive_decode_attention(
        q.reshape(B, Hkv * G, D), k, v, kv_len, scale=1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas flash prefill kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,lq,lk,window,offset", [
    (1, 4, 1, 128, 128, None, 0),
    (2, 4, 2, 256, 256, None, 0),
    (1, 8, 8, 128, 128, None, 0),          # MHA
    (1, 4, 1, 200, 200, None, 0),          # non-multiple of block
    (1, 4, 1, 256, 256, 64, 0),            # local window
    (1, 2, 1, 64, 320, None, 256),         # chunked prefill offset
])
def test_flash_prefill_vs_oracle(b, hq, hkv, lq, lk, window, offset, dtype):
    rng = jax.random.PRNGKey(lq + lk)
    ks = jax.random.split(rng, 3)
    d = 64
    q = _rand(ks[0], (b, lq, hq, d), dtype)
    k = _rand(ks[1], (b, lk, hkv, d), dtype)
    v = _rand(ks[2], (b, lk, hkv, d), dtype)
    got = flash_prefill(q, k, v, causal=True, window=window,
                        q_offset=offset)
    want = ref.naive_attention(q, k, v, causal=True, window=window,
                               q_offset=offset)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_int8_kv_cache_decode_accuracy():
    """int8 KV cache (§Perf C.4): <=3% attention-output error vs bf16."""
    from repro.configs.reduced import reduced_config
    from repro.models import attention as am
    from repro.models.common import init_params

    cfg = reduced_config("qwen2.5-3b")
    p = init_params(am.attention_specs(cfg), jax.random.PRNGKey(3))
    B, L = 2, 24
    x = (jax.random.normal(jax.random.PRNGKey(4), (B, L, cfg.d_model),
                           jnp.float32) * 0.3).astype(jnp.bfloat16)

    def run(kv_dtype):
        dt = "int8" if kv_dtype == "int8" else "bfloat16"
        c = init_params(am.kv_cache_specs(cfg, B, 32, dt),
                        jax.random.PRNGKey(0))
        outs = []
        for i in range(L):
            y, c = am.attention_decode(p, cfg, x[:, i:i + 1], c,
                                       jnp.int32(i))
            outs.append(y[:, 0])
        return jnp.stack(outs, 1).astype(jnp.float32)

    a, b = run(jnp.bfloat16), run("int8")
    rel = float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(a)))
    assert rel < 0.03, rel


def test_int8_quantize_roundtrip():
    from repro.models.attention import dequantize_kv, quantize_kv
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 2, 16),
                          jnp.float32) * 4.0
    q, s = quantize_kv(x)
    err = np.abs(np.asarray(dequantize_kv(q, s) - x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all()


def test_flash_xla_vs_naive_sweep():
    """The blocked-scan XLA path (train default) vs naive."""
    rng = jax.random.PRNGKey(5)
    for (lq, lk, w) in [(64, 64, None), (96, 96, 32), (128, 128, None)]:
        ks = jax.random.split(jax.random.fold_in(rng, lq), 3)
        q = _rand(ks[0], (2, lq, 4, 32))
        k = _rand(ks[1], (2, lk, 2, 32))
        v = _rand(ks[2], (2, lk, 2, 32))
        got = ref.flash_attention_xla(q, k, v, causal=True, window=w,
                                      block_q=32, block_k=32)
        want = ref.naive_attention(q, k, v, causal=True, window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
