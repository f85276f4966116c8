"""Split-KV combine kernel: merge S unnormalized partials (paper stage 2).

On GPU FA3 this stage runs with atomics/semaphores into the output
buffer; on TPU it is a small deterministic reduction kernel — grid over
``(B, H_kv)``, each cell loads its S partials from HBM into VMEM, merges
them with the LSE algebra in f32, and writes one normalized output tile.
Bitwise-reproducible for any split count (the fixed reduction order).

``ops.decode_attention(impl="pallas")`` runs it after
:func:`~repro.kernels.flash_decode.flash_decode_partials`; the xla impl
merges with the jnp :func:`~repro.kernels.ref.lse_combine` instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128


def _combine_kernel(acc_ref,          # (S, 1, 1, G, D) f32
                    l_ref,            # (S, 1, 1, G, LANES) f32
                    m_ref,            # (S, 1, 1, G, LANES) f32
                    o_ref):           # (1, 1, G, D)
    # the stats stay lane-replicated through the reduction: Mosaic has no
    # lowering for a reduction over a lane-0 slice, and keeping the lane
    # dim lets the weights broadcast across D as a (.., 1) column
    acc = acc_ref[:, 0, 0]                                 # (S, G, D)
    l = l_ref[:, 0, 0]                                     # (S, G, LANES)
    m = m_ref[:, 0, 0]

    m_glob = jnp.max(m, axis=0, keepdims=True)             # (1, G, LANES)
    w = jnp.exp(m - m_glob)                                # (S, G, LANES)
    num = jnp.sum(acc * w[..., :1], axis=0)                # (G, D)
    den = jnp.sum(l * w, axis=0)[:, :1]                    # (G, 1)
    o_ref[0, 0] = (num / jnp.maximum(den, 1e-30)).astype(o_ref.dtype)


def flash_combine(
    acc: jax.Array,          # (S, B, Hkv, G, D) f32 unnormalized
    l: jax.Array,            # (S, B, Hkv, G) f32
    m: jax.Array,            # (S, B, Hkv, G) f32
    *,
    out_dtype=jnp.float32,
    interpret=None,
) -> jax.Array:
    """-> (B, Hkv, G, D) normalized attention output.

    ``interpret`` resolves through :func:`repro.kernels.resolve_interpret`.
    """
    S, B, Hkv, G, D = acc.shape
    # stats lane-replicated for TPU layout (same trick as flash_decode)
    l_r = jnp.broadcast_to(l[..., None], (S, B, Hkv, G, LANES))
    m_r = jnp.broadcast_to(m[..., None], (S, B, Hkv, G, LANES))

    return pl.pallas_call(
        _combine_kernel,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((S, 1, 1, G, D), lambda b, h: (0, b, h, 0, 0)),
            pl.BlockSpec((S, 1, 1, G, LANES),
                         lambda b, h: (0, b, h, 0, 0)),
            pl.BlockSpec((S, 1, 1, G, LANES),
                         lambda b, h: (0, b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
        name=f"flash_combine_s{S}",
    )(acc, l_r, m_r)
