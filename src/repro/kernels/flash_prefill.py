"""Blocked causal flash-attention forward Pallas kernel (prefill/training fwd).

Grid ``(B, NQ, NK)`` — NK innermost ("arbitrary") carries the running
softmax state in VMEM scratch; B/NQ are parallel tiles.  Blocks keep the
``(B, L, H, D)`` layout and span the whole head dim — a q/o block is
``(1, BQ, H_Q, D)``, a K/V block ``(1, BK, H_KV, D)`` — which Mosaic
accepts for any head count (the block's last two dims equal the
array's).  The kernel walks the heads in a static loop; GQA reads KV
head ``h // group`` for query head ``h`` (no KV replication in HBM).
Supports causal masking, local windows (RecurrentGemma) and a static
``q_offset`` for chunked prefill.

Out-of-range blocks (fully above the causal diagonal / outside the window)
still DMA their KV tile but skip the FLOPs via ``pl.when`` — acceptable for
a forward demonstration kernel; the XLA path is used where autodiff or
block-sparse skipping matters.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.ref import NEG_INF

STATS_LANES = 128


def _prefill_kernel(
    q_ref,                   # (1, BQ, Hq, D) pre-scaled
    k_ref,                   # (1, BK, Hkv, D)
    v_ref,                   # (1, BK, Hkv, D)
    o_ref,                   # (1, BQ, Hq, D)
    m_scr, l_scr, acc_scr,   # (Hq, BQ, STATS_LANES) x2, (Hq, BQ, D)
    *,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    seqlen_k: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    num_q_heads, num_kv_heads = q_ref.shape[2], k_ref.shape[2]
    group = num_q_heads // num_kv_heads

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * block_q + q_offset          # absolute first q position
    k_lo = ik * block_k

    # static-shape bounds check is dynamic on grid ids -> use pl.when
    needed = jnp.bool_(True)
    if causal:
        needed &= k_lo <= q_lo + block_q - 1
    if window is not None:
        needed &= k_lo + block_k - 1 > q_lo - window

    @pl.when(needed)
    def _compute():
        qpos = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < seqlen_k
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window

        for hk in range(num_kv_heads):
            k = k_ref[0, :, hk, :].astype(jnp.float32)     # (BK, D)
            v = v_ref[0, :, hk, :].astype(jnp.float32)
            for h in range(hk * group, (hk + 1) * group):
                q = q_ref[0, :, h, :].astype(jnp.float32)  # (BQ, D)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.where(mask, s, NEG_INF)

                m_prev = m_scr[h, :, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                p = jnp.where(mask, p, 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = jnp.broadcast_to(
                    l_scr[h, :, :1] * alpha
                    + jnp.sum(p, axis=1, keepdims=True),
                    l_scr.shape[1:])
                acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(ik == num_k_blocks - 1)
    def _flush():
        for h in range(num_q_heads):
            out = acc_scr[h] / jnp.maximum(l_scr[h, :, :1], 1e-30)
            o_ref[0, :, h, :] = out.astype(o_ref.dtype)


def flash_prefill(
    q: jax.Array,            # (B, Lq, Hq, D)
    k: jax.Array,            # (B, Lk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    scale: Optional[float] = None,
    interpret=None,
) -> jax.Array:
    """Causal (optionally windowed / offset) attention -> (B, Lq, Hq, D).

    ``interpret`` resolves through :func:`repro.kernels.resolve_interpret`.
    """
    B, Lq, Hq, D = q.shape
    _, Lk, Hkv, _ = k.shape
    scale = scale if scale is not None else D ** -0.5

    block_q = min(block_q, max(8, Lq))
    block_k = min(block_k, Lk)
    pq, pk = (-Lq) % block_q, (-Lk) % block_k
    qs = q.astype(jnp.float32) * scale    # scaled q stays f32, like ref
    if pq:
        qs = jnp.pad(qs, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    NQ, NK = (Lq + pq) // block_q, (Lk + pk) // block_k

    kernel = functools.partial(
        _prefill_kernel, block_q=block_q, block_k=block_k, num_k_blocks=NK,
        causal=causal, window=window, q_offset=q_offset, seqlen_k=Lk)

    out = pl.pallas_call(
        kernel,
        grid=(B, NQ, NK),
        in_specs=[
            pl.BlockSpec((1, block_q, Hq, D), lambda b, iq, ik: (b, iq, 0, 0)),
            pl.BlockSpec((1, block_k, Hkv, D), lambda b, iq, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, block_k, Hkv, D), lambda b, iq, ik: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Hq, D),
                               lambda b, iq, ik: (b, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Lq + pq, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hq, block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((Hq, block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((Hq, block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="flash_prefill",
    )(qs, k, v)
    return out[:, :Lq]
