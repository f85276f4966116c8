"""Standard multi-head attention block (GQA/MQA/MHA) with KV cache.

Entry points, all operating on a single layer's params:

- :func:`attention_train`   — full-sequence causal (optionally windowed)
  attention for training / prefill.
- :func:`attention_decode`  — one-token cache write + decode against a
  padded KV cache, routed through the paper's split policy via
  ``kernels.ops``.

Cache layout is ``(B, L_max, H_kv, D)`` — sequence-major so the mesh-level
sequence split (serving/decode_step.py) can shard ``L_max`` directly.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.common import ParamSpec, apply_rope
from repro.plan import LaunchPlan
from repro.quant import QUANT_DTYPES, Quantizer
from repro.sharding.ctx import shard_activation

Params = Dict[str, jax.Array]


def attention_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    specs = {
        "wq": ParamSpec((d, hq, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((hq, hd, d), ("heads", "head_dim", "embed"),
                        fan_in=hq * hd),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((hq, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"),
                                init="zeros")
        specs["bv"] = ParamSpec((hkv, hd), ("kv_heads", "head_dim"),
                                init="zeros")
    return specs


def _project_qkv(params: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, rope: bool = True
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, L, d) -> q (B,L,Hq,D), k/v (B,L,Hkv,D), rope applied."""
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"])
    k = jnp.einsum("bld,dhk->blhk", x, params["wk"])
    v = jnp.einsum("bld,dhk->blhk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    if rope and cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_train(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, L, d)
    positions: jax.Array,               # (B, L) int32
    *,
    window: Optional[int] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = ops.attention(q, k, v, causal=True, window=window,
                        impl=impl or cfg.attention_impl)
    return jnp.einsum("blhk,hkd->bld", out, params["wo"])


def attention_prefill(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, L, d)
    positions: jax.Array,               # (B, L)
    cache_len: int,
    *,
    window: Optional[int] = None,
    impl: Optional[str] = None,
    kv_dtype: str = "bfloat16",
    plan: Optional[LaunchPlan] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence attention that also emits the decode cache.

    The cache is laid out exactly as the decode step expects: linear
    [0..L) for full attention, ring order (position % window) holding the
    last ``window`` positions for local attention.  A prefill-kind
    ``plan`` (the serving engine's fused-admission path) selects the
    attention impl; prefill never splits KV, so there is no frozen
    split to consume.
    """
    B, L, _ = x.shape
    if impl is None and plan is not None:
        impl = plan.impl
    q, k, v = _project_qkv(params, cfg, x, positions)
    # the prompt's K/V rows stay whole on every chip of a mesh: without
    # this anchor GSPMD carries a sequence-sharded cache's layout back
    # into the attention and splits its key axis across chips, summing
    # the softmax partials with an all-reduce (another association of
    # the f32 sums than the one-device prefill)
    k, v = (shard_activation(t, ("batch", None, "kv_heads", None))
            for t in (k, v))
    out = ops.attention(q, k, v, causal=True, window=window,
                        impl=impl or cfg.attention_impl)
    y = jnp.einsum("blhk,hkd->bld", out, params["wo"])

    if window is None:
        pad = cache_len - L
        assert pad >= 0, f"prompt ({L}) exceeds cache ({cache_len})"
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        W = cache_len                   # ring cache sized min(window, max)
        if L >= W:
            # slot s holds the unique position p in [L-W, L), p % W == s
            s_idx = jnp.arange(W)
            base = L - W
            src = base + jnp.mod(s_idx - base, W)
            kc, vc = k[:, src], v[:, src]
        else:
            pad = W - L
            kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if kv_dtype in QUANT_DTYPES:
        qz = Quantizer.from_kv_dtype(kv_dtype)
        kq, ks = qz.quantize(kc)
        vq, vs = qz.quantize(vc)
        return y, {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    return y, {"k": kc.astype(cfg.dtype), "v": vc.astype(cfg.dtype)}


def _place_rows(old: jax.Array, new: jax.Array,
                start: jax.Array) -> jax.Array:
    """Write ``new`` (B, M, ...) into ``old`` (B, V, ...) at row offset
    ``start`` (traced scalar).  ``dynamic_update_slice`` is wrong here:
    it CLAMPS the start index so a suffix landing near the view's end
    would silently shift — masked take/where places rows exactly and
    out-of-range rows keep their old values."""
    V, M = old.shape[1], new.shape[1]
    idx = jnp.arange(V)
    src = jnp.clip(idx - start, 0, M - 1)
    mask = (idx >= start) & (idx < start + M)
    moved = jnp.take(new, src, axis=1)
    mask = mask.reshape((1, V) + (1,) * (old.ndim - 2))
    return jnp.where(mask, moved.astype(old.dtype), old)


def attention_suffix_prefill(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,                       # (1, M, d) — the unshared suffix
    cache: Dict[str, jax.Array],        # slot view, prefix rows resident
    start: jax.Array,                   # scalar int32: first suffix row
    *,
    impl: Optional[str] = None,
    kv_dtype: str = "bfloat16",
    plan: Optional[LaunchPlan] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill only rows [start, start + M) against an already-resident
    prefix (prefix sharing): the view's rows [0, start) hold the adopted
    pages' K/V, the suffix queries attend over prefix + themselves via
    the causal ``q_offset`` mask, and the fresh K/V is placed into the
    view in cache layout.  Rows past ``start + M`` are garbage from the
    slot's unwritten tail — their key positions exceed every query
    position, so the same mask discards them.

    ``start`` is traced (one compiled step serves every split of a
    bucket pair), which the pallas/seqpar paths cannot consume — they
    specialize on a static ``q_offset`` — so those impls drop to the XLA
    flash reference here.
    """
    B, M, _ = x.shape
    assert B == 1, "suffix prefill is a batch-1 admission step"
    if impl is None and plan is not None:
        impl = plan.impl
    impl = impl or cfg.attention_impl
    if impl in ("pallas", "seqpar"):
        impl = "xla"
    positions = start + jnp.arange(M)[None, :]
    q, k, v = _project_qkv(params, cfg, x, positions)

    if kv_dtype in QUANT_DTYPES:
        qz = Quantizer.from_kv_dtype(kv_dtype)
        kq, ks = qz.quantize(k)
        vq, vs = qz.quantize(v)
        cache = {"k": _place_rows(cache["k"], kq, start),
                 "v": _place_rows(cache["v"], vq, start),
                 "k_s": _place_rows(cache["k_s"], ks, start),
                 "v_s": _place_rows(cache["v_s"], vs, start)}
        kf = qz.dequantize(cache["k"], cache["k_s"])
        vf = qz.dequantize(cache["v"], cache["v_s"])
    else:
        cache = {"k": _place_rows(cache["k"], k, start),
                 "v": _place_rows(cache["v"], v, start)}
        kf, vf = cache["k"], cache["v"]
    out = ops.attention(q, kf.astype(q.dtype), vf.astype(q.dtype),
                        causal=True, q_offset=start, impl=impl)
    y = jnp.einsum("blhk,hkd->bld", out, params["wo"])
    return y, cache


def cross_attention_train(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, Lq, d) decoder stream
    memory: jax.Array,                  # (B, Lk, d) encoder output
    *,
    impl: Optional[str] = None,
) -> jax.Array:
    """Encoder-decoder cross attention (no mask, no rope)."""
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"])
    k = jnp.einsum("bld,dhk->blhk", memory, params["wk"])
    v = jnp.einsum("bld,dhk->blhk", memory, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    out = ops.attention(q, k, v, causal=False,
                        impl=impl or cfg.attention_impl)
    return jnp.einsum("blhk,hkd->bld", out, params["wo"])


def precompute_cross_kv(params: Params, cfg: ModelConfig,
                        memory: jax.Array) -> Dict[str, jax.Array]:
    """Project encoder output to K/V once per request (decode fast path)."""
    k = jnp.einsum("bld,dhk->blhk", memory, params["wk"])
    v = jnp.einsum("bld,dhk->blhk", memory, params["wv"])
    if cfg.qkv_bias:
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    return {"k": k, "v": v}


def cross_attention_decode(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, 1, d)
    cross_cache: Dict[str, jax.Array],  # precomputed k/v (B, Lk, Hkv, D)
    *,
    plan: Optional[LaunchPlan] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Decode-time cross attention against a FIXED-length memory.

    L_K is the encoder length (Whisper: 1500 frames -> nblk = 12) — decode
    against it is exactly the paper's shape family, so it routes through
    the same split policy.
    """
    B = x.shape[0]
    q = jnp.einsum("bld,dhk->blhk", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
    Lk = cross_cache["k"].shape[1]
    kv_len = jnp.full((B,), Lk, jnp.int32)
    # encoder length != decoder cache length: any plan frozen for the
    # SELF-attention shape (explicit or ambient) must not apply — keep
    # only the policy/num_cores overrides
    if plan is not None and plan.frozen:
        plan = plan.context_only()
    out = ops.decode_attention(
        q[:, 0], cross_cache["k"], cross_cache["v"], kv_len,
        plan=plan, use_ctx_metadata=False,
        impl=impl or cfg.attention_impl)
    return jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None]


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Deprecated: materialize via ``repro.cache`` (``Model.init_cache``
    for the dense arrays, or a ``CacheManager`` for layout choice)."""
    import warnings
    warnings.warn(
        "attention.init_kv_cache is deprecated; go through repro.cache "
        "(Model.init_cache / Model.cache_manager)",
        DeprecationWarning, stacklevel=2)
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    if dtype in ("int8", jnp.int8):
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(shape[:3], jnp.float32),
                "v_s": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                   dtype: str = "bfloat16") -> Dict[str, ParamSpec]:
    """KV cache layout.  A quantized ``dtype`` ("int8" | "fp8") stores
    the data leaves in the :class:`~repro.quant.QuantSpec` storage dtype
    plus per-(token, head) symmetric scales — halving (or better) the
    decode step's dominant memory term (§Perf C.4).

    Leaves are marked ``paged=True``: self-attention K/V (and its
    quantization scales) is position-linear, so the ``repro.cache``
    paged layout may store it as pages when the seq axis spans the full
    slot capacity — one page table serves data and scale pools alike.
    """
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    axes = ("batch", "seq", "kv_heads", "head_dim")
    if dtype in QUANT_DTYPES:
        storage = QUANT_DTYPES[dtype].storage
        sspec = ParamSpec(shape[:3], axes[:3], dtype="float32",
                          init="zeros", paged=True)
        return {"k": ParamSpec(shape, axes, dtype=storage, init="zeros",
                               paged=True),
                "v": ParamSpec(shape, axes, dtype=storage, init="zeros",
                               paged=True),
                "k_s": sspec, "v_s": sspec}
    return {"k": ParamSpec(shape, axes, dtype=dtype, init="zeros",
                           paged=True),
            "v": ParamSpec(shape, axes, dtype=dtype, init="zeros",
                           paged=True)}


# int8 per-(token, head) transforms, kept as module-level functions for
# the many existing call sites; they delegate to the repro.quant default
# resolver (numerics pinned bit-identical by tests/test_quant.py).
_INT8 = Quantizer()


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(..., head) int8 over the feature dim.
    x: (..., H, D) -> (q int8 same shape, scale f32 (..., H))."""
    return _INT8.quantize(x)


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return _INT8.dequantize(q, scale)


def _decode_rows(params: Params, cfg: ModelConfig, x: jax.Array,
                 t: jax.Array, qz: Optional[Quantizer] = None
                 ) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """Project one new token per slot.  x: (B, 1, d), ``t`` scalar or
    (B,).  Returns (q (B, Hq, D), the new cache row of every leaf
    ``{"k", "v"}`` (B, Hkv, D) — plus ``{"k_s", "v_s"}`` (B, Hkv), the
    rows quantized by ``qz`` before the write, where the cache is
    quantized — and the positions (B,) int32)."""
    B = x.shape[0]
    tv = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    q, k, v = _project_qkv(params, cfg, x, tv[:, None])
    if qz is None:
        return q[:, 0], {"k": k[:, 0], "v": v[:, 0]}, tv
    kq, ks = qz.quantize(k[:, 0])
    vq, vs = qz.quantize(v[:, 0])
    return q[:, 0], {"k": kq, "v": vq, "k_s": ks, "v_s": vs}, tv


def _write_rows(cache: Dict[str, jax.Array], rows: Dict[str, jax.Array],
                t: jax.Array, layer: Optional[jax.Array] = None
                ) -> Dict[str, jax.Array]:
    """Write slot ``b``'s new row into every leaf at position ``t[b]``.

    Leaves are (B, L, ...), or a group's layer-stacked (layers, B, L,
    ...) with ``layer`` the row's layer: one scatter of B rows a leaf,
    which XLA performs in place on a buffer nothing else reads.  A
    position past the end lands on the last row, as
    ``dynamic_update_slice`` clamps it.
    """
    B = t.shape[0]
    L = cache["k"].shape[1 if layer is None else 2]
    idx = (jnp.arange(B), jnp.clip(t, 0, L - 1))
    if layer is not None:
        idx = (layer,) + idx
    return {name: leaf.at[idx].set(rows[name].astype(leaf.dtype),
                                   unique_indices=True)
            for name, leaf in cache.items()}


def attention_decode(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, 1, d) — the new token
    cache: Dict[str, jax.Array],
    t: jax.Array,                       # scalar int32: current position
    *,
    plan: Optional[LaunchPlan] = None,
    window: Optional[int] = None,
    impl: Optional[str] = None,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step. Returns (output (B,1,d), updated cache).

    ``t``: scalar or (B,) — position of each sequence's new token.
    ``layer``: ``cache`` is a group's whole layer-stacked cache, the new
    rows are written into it in place at ``[layer, b, t_b]``, and the
    layer is then sliced out and attended; the stack is returned.
    Without it ``cache`` is one layer's.  A sequence-sharded plan takes
    the fused ``ops.decode_attention_update`` path, which writes each
    chip's shard inside its ``shard_map`` (one layer's cache only).
    """
    q, rows, tv = _decode_rows(params, cfg, x, t, Quantizer.for_cache(cache))
    cache_len = cache["k"].shape[1 if layer is None else 2]
    # windowed layers attend over the ring cache — a different L_K than
    # the full-cache shape any frozen plan (explicit or ambient scope)
    # describes, so the frozen decision is dropped here (policy overrides
    # survive) rather than trusting every call site to know that
    use_ctx_md = window is None
    if window is not None and plan is not None and plan.frozen:
        plan = plan.context_only()
    if window is not None:
        # local attention: ring-buffer cache sized to the window.  RoPE is
        # applied at absolute positions before the write, so slot order is
        # irrelevant — every resident entry is attendable (all are past).
        write_t = jnp.mod(tv, jnp.int32(cache_len))
        kv_len = jnp.minimum(tv + 1, jnp.int32(cache_len))
    else:
        write_t = tv
        kv_len = tv + 1
    impl = impl or cfg.attention_impl
    if ops.seq_shard_plan(plan) is not None:
        assert layer is None, "a sequence-sharded cache is written per layer"
        quant = None
        if "k_s" in cache:
            quant = {"k_s": cache["k_s"], "v_s": cache["v_s"],
                     "k_ns": rows["k_s"], "v_ns": rows["v_s"]}
        out, *leaves = ops.decode_attention_update(
            q, cache["k"], cache["v"], rows["k"], rows["v"], write_t,
            kv_len, plan=plan, use_ctx_metadata=use_ctx_md, impl=impl,
            quant=quant)
        cache = dict(zip(("k", "v", "k_s", "v_s"), leaves))
    else:
        cache = _write_rows(cache, rows, write_t, layer)
        view = cache if layer is None else {
            name: jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)
            for name, leaf in cache.items()}
        # a quantized cache's scales ride in: pallas runs the fused
        # in-register-dequant kernel, xla dequantizes up front
        out = ops.decode_attention(
            q, view["k"], view["v"], kv_len, k_scale=view.get("k_s"),
            v_scale=view.get("v_s"), plan=plan,
            use_ctx_metadata=use_ctx_md, impl=impl)
    y = jnp.einsum("bhk,hkd->bd", out, params["wo"])
    return y[:, None], cache


def _place_rows_at(old: jax.Array, new: jax.Array,
                   start: jax.Array) -> jax.Array:
    """Per-slot variant of :func:`_place_rows`: write ``new`` (B, M, ...)
    into ``old`` (B, V, ...) at PER-SLOT row offsets ``start`` (B,).
    Lockstep verify batches place each slot's k+1 fresh K/V rows at that
    slot's own position, so the offset is a vector, not a scalar."""
    B, V = old.shape[:2]
    M = new.shape[1]
    idx = jnp.arange(V)[None, :]                         # (1, V)
    st = start.astype(jnp.int32)[:, None]                # (B, 1)
    src = jnp.clip(idx - st, 0, M - 1)                   # (B, V)
    mask = (idx >= st) & (idx < st + M)
    src = src.reshape((B, V) + (1,) * (old.ndim - 2))
    moved = jnp.take_along_axis(new, src, axis=1)
    mask = mask.reshape((B, V) + (1,) * (old.ndim - 2))
    return jnp.where(mask, moved.astype(old.dtype), old)


def attention_verify(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, M, d) — current token + drafts
    cache: Dict[str, jax.Array],
    t: jax.Array,                       # (B,) int32: each slot's position
    *,
    plan: Optional[LaunchPlan] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Speculative-verify attention step: score an ``M = k + 1``-row
    query block per slot in one planned launch.

    Rows land in the cache at [t, t + M) via masked per-slot placement
    (the k-row analogue of the suffix-prefill write); queries attend
    causal-within-block at the slot's absolute offset through
    :func:`ops.verify_attention`, which consumes the frozen
    ``("verify", k, bucket)`` plan.  The caller commits only accepted
    rows (paged write-back masks pages past the accept point; dense
    rollback is the host-side ``kv_len`` truncate) — rejected rows stay
    as garbage above ``kv_len``, the repo-wide masking invariant.
    """
    B, M, _ = x.shape
    tv = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    positions = tv[:, None] + jnp.arange(M, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)

    if "k_s" in cache:                      # quantized KV cache
        qz = Quantizer.for_cache(cache)
        kq, ks = qz.quantize(k_new)
        vq, vs = qz.quantize(v_new)
        cache = {"k": _place_rows_at(cache["k"], kq, tv),
                 "v": _place_rows_at(cache["v"], vq, tv),
                 "k_s": _place_rows_at(cache["k_s"], ks, tv),
                 "v_s": _place_rows_at(cache["v_s"], vs, tv)}
        kf = qz.dequantize(cache["k"], cache["k_s"])
        vf = qz.dequantize(cache["v"], cache["v_s"])
    else:
        cache = {"k": _place_rows_at(cache["k"], k_new, tv),
                 "v": _place_rows_at(cache["v"], v_new, tv)}
        kf, vf = cache["k"], cache["v"]
    out = ops.verify_attention(q, kf.astype(q.dtype), vf.astype(q.dtype),
                               tv, plan=plan,
                               impl=impl or cfg.attention_impl)
    y = jnp.einsum("bmhk,hkd->bmd", out, params["wo"])
    return y, cache
