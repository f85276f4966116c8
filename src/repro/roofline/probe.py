"""Probe-based exact cost accounting for scanned programs.

XLA's ``cost_analysis`` counts a while-loop body ONCE regardless of trip
count (verified experimentally in this repo: an 8-step scan reports 1/8
of the unrolled FLOPs).  The production programs scan over layers, so raw
numbers from the full compile are wrong by ~num_layers.

Fix: compile two PROBE variants of the same cell with the layer loop
**unrolled** (``scan_layers=False``) at 1 and 2 superblocks, naive
attention (no inner scans) and unrolled SSD chunk scans — their
difference isolates the exact per-superblock cost, and

    corrected = C1 - body + total_trips * body * adjustments

Adjustments applied analytically (documented in EXPERIMENTS.md):
- train remat ``nothing_saveable``: backward recomputes the forward
  body -> matmul-ish FLOPs x 4/3 over the no-remat probe (fwd+bwd = 3
  fwd-equivalents -> 4).
- microbatching (M > 1): per-layer FSDP param collectives (all-gather /
  reduce-scatter) happen once per microbatch -> x M; activation-sized
  collectives (all-reduce / all-to-all) track tokens -> unchanged;
  param-read bytes x M (layer param bytes known exactly from the specs).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.configs.base import (
    ModelConfig,
    OptimizerConfig,
    ServeConfig,
    ShapeConfig,
    TrainConfig,
)
from repro.models.common import is_spec
from repro.roofline.hlo import collective_bytes

Pytree = Any


@dataclass
class ProbeCost:
    flops: float
    bytes: float
    coll: Dict[str, float]

    def sub(self, o: "ProbeCost") -> "ProbeCost":
        return ProbeCost(self.flops - o.flops, self.bytes - o.bytes,
                         {k: self.coll.get(k, 0) - o.coll.get(k, 0)
                          for k in set(self.coll) | set(o.coll)})


def _pattern_len(cfg: ModelConfig) -> int:
    return len(cfg.hybrid.pattern) if cfg.family == "hybrid" else 1


def _probe_cfg(cfg: ModelConfig, n_super: int, kind: str) -> ModelConfig:
    pl = _pattern_len(cfg)
    kw = dict(
        num_layers=n_super * pl,
        scan_layers=False,
        probe_unroll=True,
        # naive attention has no inner scans -> exact counting; decode uses
        # the real split path (its collectives ARE the measurement)
        attention_impl="naive" if kind != "decode" else "xla",
    )
    if cfg.family == "encdec":
        kw["num_encoder_layers"] = n_super
        kw["num_layers"] = n_super
    return cfg.replace(**kw)


def _measure(arch_cfg: ModelConfig, shape: ShapeConfig, mesh, policy: str
             ) -> ProbeCost:
    """Lower+compile one probe variant; extract flops/bytes/collectives."""
    from repro.models.registry import Model
    from repro.serving.decode_step import build_mesh_decode_step, build_prefill_step
    from repro.training.train_step import build_train_step

    model = Model(arch_cfg)
    if shape.kind == "train":
        tcfg = TrainConfig(model=arch_cfg, shape=shape,
                           optimizer=OptimizerConfig(),
                           microbatches=1, remat_policy="none")
        bundle = build_train_step(model, tcfg, mesh)
    elif shape.kind == "prefill":
        scfg = ServeConfig(model=arch_cfg, shape=shape, split_policy=policy)
        bundle = build_prefill_step(model, scfg, mesh)
    else:
        scfg = ServeConfig(model=arch_cfg, shape=shape, split_policy=policy)
        bundle = build_mesh_decode_step(model, scfg, mesh)
    compiled = bundle.step.lower(*bundle.abstract_args()).compile()
    cost = dict(compiled.cost_analysis() or {})
    coll = collective_bytes(compiled.as_text())
    return ProbeCost(float(cost.get("flops", 0.0)),
                     float(cost.get("bytes accessed", 0.0)),
                     {k: float(v) for k, v in coll.items()})


def layer_param_bytes(cfg: ModelConfig) -> float:
    """bf16 bytes of ONE superblock's params (for the micro correction)."""
    from repro.models.lm import block_specs, layer_groups
    import jax

    if cfg.family == "encdec":
        from repro.models.encdec import _dec_block_specs, _enc_block_specs
        specs = {"e": _enc_block_specs(cfg), "d": _dec_block_specs(cfg)}
    else:
        pattern = layer_groups(cfg)[0][0]
        specs = {f"k{i}": block_specs(cfg, k)
                 for i, k in enumerate(pattern)}
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    return float(sum(int(np.prod(s.shape)) * 2 for s in leaves))


@dataclass
class CorrectedCost:
    flops: float                       # per-device
    bytes: float
    coll: Dict[str, float]
    trips: float
    body: ProbeCost
    nonloop: ProbeCost


def attention_stream_bytes(cfg: ModelConfig, shape: ShapeConfig, mesh,
                           *, block_q: int = 512) -> float:
    """Analytic per-device K/V/Q streaming bytes of ONE superblock's
    attention at full sequence (train/prefill).

    The flash-xla probe undercounts these (its KV loop body is counted
    once); everything it streams is re-derived here: each of ``nq`` query
    blocks re-reads K and V (causal ~halves it), Q and the output are
    touched once.
    """
    if cfg.family == "ssm":
        return 0.0
    ndev = mesh.devices.size
    model_ax = mesh.shape["model"]
    data_sz = ndev // model_ax
    B, L = shape.global_batch, shape.seq_len
    b_dev = B // data_sz if B % data_sz == 0 else B
    dt = 2  # bf16
    # heads that don't divide the axis run sequence-parallel attention
    # (ops.AttnContext): each chip streams K/V for its OWN q chunk only
    seqpar = cfg.num_heads % model_ax != 0

    def one_attn(lq, lk, hq, hkv, dqk, dv, causal, window=None):
        hq_d = hq // model_ax if hq % model_ax == 0 else hq
        hkv_d = hkv // model_ax if hkv % model_ax == 0 else hkv
        nq = -(-lq // block_q)
        if seqpar and lq % model_ax == 0:
            nq = max(1, nq // model_ax)
        lk_eff = min(lk, (window or lk) + block_q)
        cf = 0.5 if (causal and window is None and lq == lk) else 1.0
        kv = nq * lk_eff * hkv_d * (dqk + dv) * dt * cf
        qo = lq * hq_d * (dqk + dv) * dt / (model_ax if seqpar else 1)
        return b_dev * (kv + qo)

    hd = cfg.resolved_head_dim
    total = 0.0
    if cfg.family == "encdec":
        # encoder self (bidirectional) + decoder self + cross, one of each
        T = cfg.encoder_positions
        total += one_attn(T, T, cfg.num_heads, cfg.num_kv_heads, hd, hd,
                          causal=False)
        total += one_attn(L, L, cfg.num_heads, cfg.num_kv_heads, hd, hd,
                          causal=True)
        total += one_attn(L, T, cfg.num_heads, cfg.num_kv_heads, hd, hd,
                          causal=False)
        return total
    if cfg.mla is not None:
        m = cfg.mla
        dqk = m.qk_nope_head_dim + m.qk_rope_head_dim
        return one_attn(L, L, cfg.num_heads, cfg.num_heads, dqk,
                        m.v_head_dim, causal=True)
    if cfg.family == "hybrid":
        # one windowed attention per superblock (pattern has 1 attn layer)
        n_attn = sum(1 for k in cfg.hybrid.pattern if k == "attn")
        return n_attn * one_attn(L, L, cfg.num_heads, cfg.num_kv_heads,
                                 hd, hd, causal=True,
                                 window=cfg.hybrid.window)
    return one_attn(L, L, cfg.num_heads, cfg.num_kv_heads, hd, hd,
                    causal=True)


def _sharded_bytes_per_device(specs: Pytree, mesh, rules) -> float:
    """Exact per-device bytes of a spec tree under the given rules."""
    import jax
    from repro.sharding.rules import spec_for

    total = 0.0
    for s in jax.tree_util.tree_leaves(specs, is_leaf=is_spec):
        nbytes = float(np.prod(s.shape)) * np.dtype(s.jdtype).itemsize
        pspec = spec_for(s.shape, s.axes, rules, mesh)
        shards = 1
        for entry in pspec:
            if entry is None:
                continue
            for ax in ((entry,) if isinstance(entry, str) else entry):
                shards *= mesh.shape[ax]
        total += nbytes / shards
    return total


# modeled activation touches per layer per forward pass (reads+writes of
# (tokens, d_model)-sized tensors through norms/projections/residuals)
_ACT_TOUCHES = {"dense": 16, "vlm": 16, "moe": 28, "mla": 22,
                "ssm": 30, "hybrid": 20, "encdec": 24}


def analytic_memory_bytes(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                          microbatches: int, kind: str,
                          seq_split: bool = False,
                          kv_dtype: str = "bfloat16") -> float:
    """Modeled per-device HBM bytes for one step (see EXPERIMENTS.md).

    CPU-backend ``bytes accessed`` reflects the weakly-fused CPU HLO (15x
    the TPU traffic in our measurements), so the memory term is modeled:
    parameter passes (FSDP-gathered for train, TP-resident for serve),
    optimizer state, activation touches with remat, attention streaming,
    logits/loss, and KV-cache traffic — all from the specs, exactly.
    """
    import jax
    from repro.models.registry import Model
    from repro.serving.decode_step import serve_param_rules
    from repro.sharding.rules import cache_rules, param_rules

    model = Model(cfg)
    ndev = mesh.devices.size
    model_ax = mesh.shape["model"]
    data_sz = ndev // model_ax
    B, L = shape.global_batch, shape.seq_len
    tokens_dev = B * L / data_sz if kind != "decode" else B / data_sz
    d = cfg.d_model
    vshard = cfg.vocab_size / (model_ax if cfg.vocab_size % model_ax == 0
                               else 1)
    specs = model.param_specs()
    touches = _ACT_TOUCHES.get(cfg.family, 16)

    if kind == "train":
        M = max(1, microbatches)
        # FSDP: every device materializes+reads the FULL layer params per
        # microbatch per pass (fwd, remat-fwd, bwd)
        p_full = float(sum(np.prod(s.shape) * 2 for s, _ in
                           _iter_specs_bytes(specs)))
        param_traffic = 3.0 * M * p_full
        p_dev = _sharded_bytes_per_device(specs, mesh, param_rules())
        opt_traffic = 6.0 * p_dev * 2.0     # m, v, p read+write (f32~2xbf16)
        act = tokens_dev * d * 2 * touches * cfg.num_layers * 3.0
        attn = attention_stream_bytes(cfg, shape, mesh) \
            * (cfg.num_layers / _pattern_len(cfg)) * 3.0
        loss = tokens_dev * vshard * 4 * 4.0
        return param_traffic + opt_traffic + act + attn + loss

    p_dev = _sharded_bytes_per_device(specs, mesh, serve_param_rules())
    if cfg.moe is not None and kind == "decode":
        # decode touches only the routed experts' weights
        frac = min(1.0, B * cfg.moe.top_k / cfg.moe.num_experts)
        p_dev *= max(frac, 0.1)
    cache = _sharded_bytes_per_device(
        model.cache_specs(B, max(L, 1), kv_dtype), mesh,
        cache_rules(seq_split))

    if kind == "prefill":
        act = tokens_dev * d * 2 * touches * cfg.num_layers
        attn = attention_stream_bytes(cfg, shape, mesh) \
            * (cfg.num_layers / _pattern_len(cfg))
        return p_dev + act + attn + cache + tokens_dev / L * vshard * 4
    # decode: read params + read whole cache + write one entry
    act = tokens_dev * d * 2 * touches * cfg.num_layers
    return p_dev + cache + act + B / data_sz * vshard * 4


def _iter_specs_bytes(specs):
    import jax
    for s in jax.tree_util.tree_leaves(specs, is_leaf=is_spec):
        yield s, s.axes


def corrected_cost(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                   policy: str = "paper", microbatches: int = 1,
                   remat: bool = True, seq_split: bool = False,
                   kv_dtype: str = "bfloat16") -> CorrectedCost:
    """FLOPs from the unrolled naive-attention probe pair (loop-free ->
    exact); collectives from the flash-attention probe pair (naive
    probes materialize L^2 score tensors that GSPMD then reshards —
    64 GiB phantom all-gathers measured on the MoE cell); memory term
    from the analytic model (CPU bytes-accessed reflects weak CPU
    fusion, not TPU HBM traffic).
    """
    cA1 = _measure(_probe_cfg(cfg, 1, shape.kind), shape, mesh, policy)
    cA2 = _measure(_probe_cfg(cfg, 2, shape.kind), shape, mesh, policy)
    bodyA = cA2.sub(cA1)
    nonloopA = cA1.sub(bodyA)

    if shape.kind != "decode":
        fl1 = dataclasses.replace(_probe_cfg(cfg, 1, shape.kind),
                                  attention_impl="xla")
        fl2 = dataclasses.replace(_probe_cfg(cfg, 2, shape.kind),
                                  attention_impl="xla")
        cB1 = _measure(fl1, shape, mesh, policy)
        cB2 = _measure(fl2, shape, mesh, policy)
        bodyC = cB2.sub(cB1)
        nonloopC = cB1.sub(bodyC)
    else:
        bodyC, nonloopC = bodyA, nonloopA

    pl = _pattern_len(cfg)
    trips = cfg.num_layers / pl        # fractional remainder approximated

    is_train = shape.kind == "train"
    remat_f = (4.0 / 3.0) if (is_train and remat) else 1.0
    M = max(1, microbatches) if is_train else 1

    flops = nonloopA.flops + trips * bodyA.flops * remat_f
    bytes_ = analytic_memory_bytes(cfg, shape, mesh, microbatches=M,
                                   kind=shape.kind, seq_split=seq_split,
                                   kv_dtype=kv_dtype)

    coll: Dict[str, float] = {}
    for cat in set(bodyC.coll) | set(nonloopC.coll):
        b = bodyC.coll.get(cat, 0.0)
        if cat in ("all-gather", "reduce-scatter") and M > 1 and is_train:
            b *= M
        coll[cat] = nonloopC.coll.get(cat, 0.0) + trips * b
    return CorrectedCost(flops, bytes_, coll, trips, bodyA, nonloopA)
