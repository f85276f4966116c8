"""Split-KV Flash-Decoding Pallas TPU kernel (the paper's target kernel).

Grid layout ``(B, S, NB)``:

- ``B, S`` are *parallel* dimensions — the work tiles the scheduler
  distributes; ``S`` is the sequence-split axis the paper's policy sizes.
- ``NB`` (KV blocks within one split) is the innermost *arbitrary*
  dimension: a float32 running-softmax state lives in VMEM scratch and is
  carried across NB steps (classic flash accumulation).

The cache keeps the engine's ``(B, L, H_KV, D)`` layout.  A K/V block is
``(1, BK, H_KV, D)``: it spans the whole KV-head dim, which Mosaic
accepts for any head count because the block's last two dims equal the
array's, and one DMA brings every head's rows of the block.  The kernel
walks the heads in a static loop, so each KV byte is read once per step.

GQA packing (the paper's ``pack_gqa=True``): the ``G = H_Q/H_KV`` query
heads of one group ride the MXU M-dimension as a single ``(G, D) @ (D, BK)``
matmul — one tile per (batch, kv-head) instead of G.

Each (b, s) cell emits an *unnormalized* partial ``(acc, l, m)`` per KV
head; a separate LSE-combine stage merges the S partials.  On GPU FA3
this combine uses atomics/semaphores; on TPU it is a deterministic
reduction — decode results are bitwise-reproducible for any split count
(tested).

VMEM per grid cell at qwen2.5-3b widths (BK=128, H_KV=2, G=8, D=128,
bf16 K/V, f32 state): two double-buffered K/V blocks, whose 2-row head
dim pads to a full 16-row bf16 tile (4 x 512 KiB), plus ~40 KiB of
state — inside the default scoped VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.ref import NEG_INF

DEFAULT_BLOCK_K = 128
STATS_LANES = 128            # stats stored lane-replicated for TPU layout


def _decode_kernel(
    kv_len_ref,              # scalar prefetch: (B,) int32 in SMEM
    q_ref,                   # (1, Hkv, G, D) — pre-scaled f32/bf16
    k_ref,                   # (1, BK, Hkv, D) bf16 / f32 / int8 / fp8
    v_ref,                   # (1, BK, Hkv, D)
    *rest,
    num_blocks_per_split: int,
    block_k: int,
    quantized: bool,
):
    """Flash accumulation of one KV block for every KV head.

    With ``quantized`` two more inputs follow ``v_ref``: the per-(row,
    head) f32 scales ``(1, BK, Hkv)`` of K and V, applied in-register
    (the same transform as ``Quantizer.dequantize``) — HBM streams one
    byte per element plus a ``4/D`` fraction of scale bytes, and the rest
    of the accumulation is identical to attending the dequantized arrays.
    Scales of unallocated tail rows are masked by the same ``pos <
    kv_len`` predicate as the data, so poisoned (finite) page tails never
    reach the output.

    Outputs ``(1, 1, Hkv, G, D)`` acc and ``(1, 1, Hkv, G, LANES)`` l/m;
    scratch ``(Hkv, G, LANES)`` m/l and ``(Hkv, G, D)`` acc.
    """
    if quantized:
        ks_ref, vs_ref, *rest = rest
    acc_out_ref, l_out_ref, m_out_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    s = pl.program_id(1)
    nb = pl.program_id(2)
    num_kv_heads, group = q_ref.shape[1], q_ref.shape[2]

    @pl.when(nb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # mask cache positions beyond the valid length
    blk_idx = s * num_blocks_per_split + nb
    pos = blk_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (group, block_k), 1)                    # (G, BK)
    valid = pos < kv_len_ref[b]
    if quantized:
        k_sc = ks_ref[0]                                   # (BK, Hkv)
        v_sc = vs_ref[0]

    for h in range(num_kv_heads):
        q = q_ref[0, h].astype(jnp.float32)                # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)          # (BK, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quantized:
            k = k * k_sc[:, h:h + 1]
            v = v * v_sc[:, h:h + 1]

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (G, BK)
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_scr[h, :, :1]                           # (G, 1)
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        p = jnp.where(valid, p, 0.0)                       # kill exp(-inf - -inf)
        alpha = jnp.exp(m_prev - m_new)                    # (G, 1)

        l_new = l_scr[h, :, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(nb == num_blocks_per_split - 1)
    def _flush():
        acc_out_ref[0, 0] = acc_scr[...]
        l_out_ref[0, 0] = l_scr[...]
        m_out_ref[0, 0] = m_scr[...]


def _split_kv_call(q, k, v, scales, kv_len, *, num_splits: int,
                   block_k: int, interpret, name: str):
    """Shared ``pallas_call`` of the bf16 and quantized kernels.

    ``scales`` is ``()`` or ``(k_scale, v_scale)``.  Returns ``(acc, l,
    m)`` in the ``(S, B, Hkv, G, ...)`` layout of
    :func:`repro.kernels.ref.lse_combine`."""
    B, Hkv, G, D = q.shape
    _, L, _, _ = k.shape
    S = num_splits
    assert L % block_k == 0, f"pad L ({L}) to block_k ({block_k})"
    nblk = L // block_k
    assert nblk % S == 0, f"pad blocks ({nblk}) to splits ({S})"
    NB = nblk // S

    kernel = functools.partial(
        _decode_kernel, num_blocks_per_split=NB, block_k=block_k,
        quantized=bool(scales))

    def kv_block(b, s, nb, kvl):
        return (b, s * NB + nb, 0, 0)

    def scale_block(b, s, nb, kvl):
        return (b, s * NB + nb, 0)

    def part_block(b, s, nb, kvl):
        return (b, s, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hkv, G, D), lambda b, s, nb, kvl: (b, 0, 0, 0)),
        pl.BlockSpec((1, block_k, Hkv, D), kv_block),
        pl.BlockSpec((1, block_k, Hkv, D), kv_block),
    ] + [pl.BlockSpec((1, block_k, Hkv), scale_block)] * len(scales)
    acc, l, m = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S, NB),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, Hkv, G, D), part_block),
                pl.BlockSpec((1, 1, Hkv, G, STATS_LANES), part_block),
                pl.BlockSpec((1, 1, Hkv, G, STATS_LANES), part_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((Hkv, G, STATS_LANES), jnp.float32),
                pltpu.VMEM((Hkv, G, STATS_LANES), jnp.float32),
                pltpu.VMEM((Hkv, G, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Hkv, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, S, Hkv, G, STATS_LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, S, Hkv, G, STATS_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=f"{name}_s{S}",
    )(kv_len.astype(jnp.int32), q, k, v,
      *(x.astype(jnp.float32) for x in scales))

    # -> (S, B, Hkv, G, ...) layout expected by lse_combine
    acc = acc.transpose(1, 0, 2, 3, 4)
    l = l[..., 0].transpose(1, 0, 2, 3)
    m = m[..., 0].transpose(1, 0, 2, 3)
    return acc, l, m


def flash_decode_partials(
    q: jax.Array,            # (B, Hkv, G, D) — already GQA-packed & scaled
    k: jax.Array,            # (B, L_pad, Hkv, D)
    v: jax.Array,
    kv_len: jax.Array,       # (B,) int32
    *,
    num_splits: int,
    block_k: int = DEFAULT_BLOCK_K,
    interpret=None,
):
    """Run the split-KV kernel; returns unnormalized partials.

    Returns ``(acc, l, m)`` with shapes ``(S,B,Hkv,G,D)``, ``(S,B,Hkv,G)``,
    ``(S,B,Hkv,G)`` matching :func:`repro.kernels.ref.lse_combine`.
    ``interpret`` resolves through :func:`repro.kernels.resolve_interpret`.
    """
    return _split_kv_call(q, k, v, (), kv_len, num_splits=num_splits,
                          block_k=block_k, interpret=interpret,
                          name="flash_decode")


def flash_decode_quant_partials(
    q: jax.Array,            # (B, Hkv, G, D) — already GQA-packed & scaled
    k: jax.Array,            # (B, L_pad, Hkv, D) int8 / float8_e4m3fn
    v: jax.Array,
    k_scale: jax.Array,      # (B, L_pad, Hkv) f32
    v_scale: jax.Array,
    kv_len: jax.Array,       # (B,) int32
    *,
    num_splits: int,
    block_k: int = DEFAULT_BLOCK_K,
    interpret=None,
):
    """Split-KV kernel over a quantized cache; unnormalized partials.

    Same grid, accumulation and return layout as
    :func:`flash_decode_partials`; K/V blocks arrive in storage dtype and
    are dequantized in-register against their per-row scale blocks.
    """
    return _split_kv_call(q, k, v, (k_scale, v_scale), kv_len,
                          num_splits=num_splits, block_k=block_k,
                          interpret=interpret, name="flash_decode_quant")
