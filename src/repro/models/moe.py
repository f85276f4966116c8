"""Mixture-of-Experts FFN: top-k routing with capacity-based dispatch.

Two dispatch implementations, A/B-able (the MoE hillclimb cell in §Perf
swaps them and measures the HLO-FLOP delta):

- ``einsum``  — classic Switch/Mesh-TF one-hot dispatch+combine einsums.
  Simple, robustly shardable, but spends O(S·E·C·d) FLOPs moving tokens.
- ``gather``  — sort-free gather/scatter dispatch: token→slot indices are
  computed with cumulative one-hot ranks, tokens move via ``take`` /
  ``segment-style`` scatter-add. Near-zero dispatch FLOPs; this is the
  beyond-paper optimized path.

Routing is per *group* (a contiguous slab of tokens, default one batch
row) so dispatch never crosses the data-parallel shard boundary: groups
ride the batch axis, experts ride the model axis (expert parallelism
folded into TP, per DESIGN.md).

Load-balance aux loss (Switch-style) is returned alongside the output.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec

Params = Dict[str, jax.Array]


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    assert cfg.moe is not None
    d, e, f = cfg.d_model, cfg.moe.num_experts, cfg.moe.d_expert
    return {
        "router": ParamSpec((d, e), ("embed", "experts"), dtype="float32"),
        "wi_gate": ParamSpec((e, d, f), ("experts", "embed", "ff"),
                             fan_in=d),
        "wi_up": ParamSpec((e, d, f), ("experts", "embed", "ff"), fan_in=d),
        "wo": ParamSpec((e, f, d), ("experts", "ff", "embed"), fan_in=f),
    }


def _capacity(group_size: int, num_experts: int, top_k: int,
              capacity_factor: float) -> int:
    c = math.ceil(capacity_factor * group_size * top_k / num_experts)
    return max(1, c)


def _route(params: Params, cfg: ModelConfig, x: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (G, S, d) -> (gates (G,S,k), experts (G,S,k) int32, aux loss)."""
    moe = cfg.moe
    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32),
                        params["router"])                        # (G,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, moe.top_k)             # (G,S,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss: E * mean_e(frac_tokens_e * mean_prob_e)
    sel = jax.nn.one_hot(experts[..., 0], moe.num_experts)       # top-1 frac
    frac = sel.mean(axis=(0, 1))
    mean_p = probs.mean(axis=(0, 1))
    aux = moe.num_experts * jnp.sum(frac * mean_p)
    return gates, experts, aux


def _expert_ffn(params: Params, xin: jax.Array) -> jax.Array:
    """xin: (..., E, C, d) -> (..., E, C, d) through per-expert SwiGLU."""
    g = jax.nn.silu(jnp.einsum("...ecd,edf->...ecf", xin, params["wi_gate"]))
    u = jnp.einsum("...ecd,edf->...ecf", xin, params["wi_up"])
    return jnp.einsum("...ecf,efd->...ecd", g * u, params["wo"])


# ---------------------------------------------------------------------------
# einsum dispatch (baseline)
# ---------------------------------------------------------------------------


def _positions_in_expert(experts: jax.Array, num_experts: int
                         ) -> Tuple[jax.Array, jax.Array]:
    """Queue position of each (token, k) routing decision within its expert.

    k-major priority: all top-1 choices get queue slots before any top-2
    choice, so capacity overflow drops the least-confident assignments.
    Returns (onehot (G,S,k,E) int32, pos (G,S,k) int32).
    """
    G, S, K = experts.shape
    onehot = jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)
    flat = onehot.transpose(0, 2, 1, 3).reshape(G, K * S, num_experts)
    pos_flat = jnp.cumsum(flat, axis=1) - 1                      # (G,kS,E)
    pos = pos_flat.reshape(G, K, S, num_experts).transpose(0, 2, 1, 3)
    pos = (pos * onehot).sum(-1)                                 # (G,S,k)
    return onehot, pos


def _moe_einsum(params: Params, cfg: ModelConfig, x: jax.Array,
                capacity: int) -> Tuple[jax.Array, jax.Array]:
    """x: (G, S, d). Returns (out (G,S,d), aux).

    The k axis is a *static Python loop* (k <= 8): materializing the
    (G,S,k,E,C) product would be ~TBs at full scale; per-k (G,S,E,C)
    dispatch tensors are transient and fuse into their einsums.
    """
    moe = cfg.moe
    G, S, d = x.shape
    E, C = moe.num_experts, capacity
    gates, experts, aux = _route(params, cfg, x)
    onehot, pos = _positions_in_expert(experts, E)
    keep = pos < C

    xin = jnp.zeros((G, E, C, d), x.dtype)
    disp_ks = []
    for ki in range(moe.top_k):
        disp_k = (onehot[:, :, ki].astype(x.dtype)[..., None]
                  * jax.nn.one_hot(jnp.where(keep[:, :, ki], pos[:, :, ki], 0),
                                   C, dtype=x.dtype)[:, :, None, :]
                  * keep[:, :, ki, None, None].astype(x.dtype))  # (G,S,E,C)
        disp_ks.append(disp_k)
        xin = xin + jnp.einsum("gsec,gsd->gecd", disp_k, x)
    xout = _expert_ffn(params, xin)
    out = jnp.zeros_like(x)
    for ki in range(moe.top_k):
        comb_k = disp_ks[ki] * gates[:, :, ki, None, None].astype(x.dtype)
        out = out + jnp.einsum("gsec,gecd->gsd", comb_k, xout)
    return out, aux


# ---------------------------------------------------------------------------
# gather dispatch (optimized: no O(S·E·C·d) one-hot matmuls)
# ---------------------------------------------------------------------------


def _moe_gather(params: Params, cfg: ModelConfig, x: jax.Array,
                capacity: int) -> Tuple[jax.Array, jax.Array]:
    moe = cfg.moe
    G, S, d = x.shape
    E, C, K = moe.num_experts, capacity, moe.top_k
    gates, experts, aux = _route(params, cfg, x)
    _, pos = _positions_in_expert(experts, E)
    keep = pos < C
    slot = experts * C + jnp.where(keep, pos, C)                 # (G,S,k)
    slot = jnp.where(keep, slot, E * C)                          # overflow slot

    def per_group(xg, slotg, gateg):
        # xg (S,d), slotg/gateg (S,k)
        src = jnp.repeat(jnp.arange(S), K)                       # (S*k,)
        flat_slot = slotg.reshape(-1)                            # (S*k,)
        buf = jnp.zeros((E * C + 1, d), xg.dtype)
        buf = buf.at[flat_slot].set(xg[src], mode="drop")        # dispatch
        xin = buf[:E * C].reshape(E, C, d)
        xout = _expert_ffn(params, xin).reshape(E * C, d)
        xout = jnp.concatenate([xout, jnp.zeros((1, d), xout.dtype)])
        picked = xout[flat_slot].reshape(S, K, d)                # combine
        return (picked * gateg[..., None].astype(xg.dtype)).sum(1)

    out = jax.vmap(per_group)(x, slot, gates)
    return out, aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (optimized: explicit all_to_all routing)
# ---------------------------------------------------------------------------


def _moe_ep_shard_map(params: Params, cfg: ModelConfig, x: jax.Array,
                      mesh, axis: str = "model"
                      ) -> Tuple[jax.Array, jax.Array]:
    """GShard-style EP: tokens split over the model axis, experts live
    sharded, two all_to_alls move only the routed tokens.

    The §Perf hillclimb B path: the auto-SPMD gather dispatch replicates
    its scatter buffers over the mesh (176 s/step of modeled collective
    time on qwen3-moe train_4k); here the wire carries exactly
    2 x (E, C_local, d) per layer plus the output all-gather.
    """
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    B, L, d = x.shape
    n = mesh.shape[axis]
    E, K = moe.num_experts, moe.top_k
    e_pad = -(-E // n) * n                 # pad experts to the axis (40->48)

    def padded(w):
        if e_pad == E:
            return w
        return jnp.pad(w, ((0, e_pad - E),) + ((0, 0),) * (w.ndim - 1))

    router = jnp.pad(params["router"], ((0, 0), (0, e_pad - E)),
                     constant_values=-1e9) if e_pad != E else params["router"]
    wi_g, wi_u, wo = (padded(params["wi_gate"]), padded(params["wi_up"]),
                      padded(params["wo"]))

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = data_axes if (data_axes and B % _prodi(
        mesh.shape[a] for a in data_axes) == 0) else None

    s_loc = (B if bspec is None else B // _prodi(
        mesh.shape[a] for a in data_axes)) * (L // n)
    cap = _capacity(s_loc, e_pad, K, moe.capacity_factor)

    def body(xb, rtr, wg, wu, wo_):
        # xb: (B_loc, L/n, d); experts for THIS device: e_pad/n.
        # Expert weights arrive in their stored FSDP layout (d sharded on
        # the data axes) and are gathered HERE — handing GSPMD a
        # replicated in_spec instead makes it rematerialize the FULL
        # expert stack per device (63.8 TB/step of all-gather, measured).
        if data_axes:
            wg = jax.lax.all_gather(wg, data_axes, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, data_axes, axis=1, tiled=True)
            wo_ = jax.lax.all_gather(wo_, data_axes, axis=2, tiled=True)
        Bl, Ll, _ = xb.shape
        S = Bl * Ll
        xt = xb.reshape(S, d)
        logits = (xt.astype(jnp.float32) @ rtr)         # (S, e_pad)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, K)        # (S, K)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        # aux loss (local estimate, pmean'd below)
        sel = jax.nn.one_hot(experts[..., 0], e_pad)
        aux = e_pad * jnp.sum(sel.mean(0) * probs.mean(0))
        aux = jax.lax.pmean(jax.lax.pmean(aux, axis),
                            data_axes) if data_axes else \
            jax.lax.pmean(aux, axis)

        # queue positions (k-major priority), slot = e * cap + pos
        onehot = jax.nn.one_hot(experts, e_pad, dtype=jnp.int32)  # (S,K,E)
        flat = onehot.transpose(1, 0, 2).reshape(K * S, e_pad)
        pos = (jnp.cumsum(flat, axis=0) - 1).reshape(K, S, e_pad)
        pos = (pos.transpose(1, 0, 2) * onehot).sum(-1)  # (S,K)
        keep = pos < cap
        slot = jnp.where(keep, experts * cap + pos, e_pad * cap)

        src = jnp.repeat(jnp.arange(S), K)
        buf = jnp.zeros((e_pad * cap + 1, d), xt.dtype)
        buf = buf.at[slot.reshape(-1)].set(xt[src], mode="drop")
        buf = buf[:-1].reshape(e_pad, cap, d)

        # ship token slabs to their expert owners
        recv = jax.lax.all_to_all(buf, axis, split_axis=0,
                                  concat_axis=1, tiled=True)
        # recv: (e_pad/n, n*cap, d) — this device's experts, all peers
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg))
        u = jnp.einsum("ecd,edf->ecf", recv, wu)
        yout = jnp.einsum("ecf,efd->ecd", g * u, wo_)
        back = jax.lax.all_to_all(yout, axis, split_axis=1,
                                  concat_axis=0, tiled=True)
        back = back.reshape(e_pad * cap, d)
        back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)])

        picked = back[slot.reshape(-1)].reshape(S, K, d)
        out = (picked * gates[..., None].astype(xt.dtype)).sum(1)
        return out.reshape(Bl, Ll, d), aux

    dspec = data_axes if data_axes else None
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, axis, None),
                  P(None, None),        # router: replicated (routing is global)
                  P(axis, dspec, None),  # stored FSDP layout (see body)
                  P(axis, dspec, None),
                  P(axis, None, dspec)),
        out_specs=(P(bspec, axis, None), P()),
        check_vma=False)
    return fn(x, router, wi_g, wi_u, wo)


def _prodi(it) -> int:
    r = 1
    for v in it:
        r *= v
    return r


def apply_moe(params: Params, cfg: ModelConfig, x: jax.Array,
              *, dispatch: str | None = None, group_size: int = 0
              ) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN over (B, L, d) activations. Returns (out, aux_loss).

    Groups are (B, L) rows by default (group = one sequence), keeping
    routing local to the data shard.  ``dispatch`` defaults to the config's
    choice (production default: ``ep_shard_map`` when a mesh context with
    a non-trivial model axis is active, else ``gather``).
    """
    B, L, d = x.shape
    moe = cfg.moe
    if dispatch is None:
        dispatch = getattr(moe, "dispatch", "gather")
    if dispatch == "ep_shard_map":
        from repro.sharding.ctx import current_mesh
        mesh = current_mesh()
        if (mesh is not None and "model" in mesh.axis_names
                and mesh.shape["model"] > 1 and L % mesh.shape["model"] == 0):
            return _moe_ep_shard_map(params, cfg, x, mesh)
        dispatch = "gather"                 # single-device fallback
    if group_size and group_size < L:
        ng = L // group_size
        xg = x.reshape(B * ng, group_size, d)
    else:
        group_size = L
        xg = x.reshape(B, L, d)
    cap = _capacity(group_size, moe.num_experts, moe.top_k,
                    moe.capacity_factor)
    fn = _moe_gather if dispatch == "gather" else _moe_einsum
    out, aux = fn(params, cfg, xg, cap)
    return out.reshape(B, L, d), aux
