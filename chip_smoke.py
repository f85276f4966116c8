"""Smoke run of the serving path on a TPU: the quickest proof that the
system starts on the chip and serves correctly.

    python chip_smoke.py [--seed N]         # one chip
    python chip_smoke.py --chips 4 [--seed N]

One chip: qwen2.5-3b at its published widths (36 layers, d_model 2048,
16 Q / 2 KV heads, head_dim 128, d_ff 11008, vocab 151936, bf16), random
weights initialised on the device from ``--seed``, is served through the
launcher's engine construction (``repro.launch.serve``): 8 requests with
64-512-token prompts and 32 greedy new tokens each, 4 slots, a 4096-row
dense cache, fused prefill, the metadata (frozen-plan) path.  It serves
them once with ``attention_impl="xla"`` and once with ``"pallas"`` on the
same params, then checks the Pallas kernels against the XLA path on the
served cache's real K/V rows (decode attention under the engine's frozen
plan, and causal prefill attention).

``--chips 4``: only the ``repro.shard`` topologies users serve
(``serve --mesh dp,sp``) — sp=4 sequence-sharded decode and dp=4 slot
shards — each against a one-device engine on the same requests, whose
greedy tokens they must match; per-device memory shows where the weights
and the KV cache landed.

Every printed time is the wall clock of this one run (compiles included
unless labelled otherwise): a smoke run, not a benchmark.  The script
refuses to run without a TPU, exits non-zero on any failure, and prints
as its last line ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "qwen2.5-3b"
NUM_REQUESTS = 8
PROMPT_LEN = (64, 513)       # prompt lengths drawn from [64, 512]
MAX_NEW = 32
SLOTS = 4
MAX_LEN = 4096
# one plan bucket per 512 rows: 64-512-token prompts fit one prefill
# program and at most two decode programs per attention impl
BUCKET = 512
# bf16 tolerance for Pallas vs XLA attention outputs (same inputs, f32
# accumulation in both, bf16 results): max |pallas - xla| <= ATOL + RTOL *
# max |xla|.  The bound scales with the largest output, not each element:
# an output is a softmax-weighted mean of V rows, and the MXU's bf16
# passes over the probabilities err in proportion to the V rows'
# magnitude, so an element that cancels to near zero carries the same
# absolute error as the largest one.
ATOL = RTOL = 2e-2
CHECK_LAYERS = (0, 17, 35)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    """The devices to run on; exits non-zero without enough TPU chips
    (a CPU fallback would print numbers for the wrong device)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found only "
                 f"{devices[0].platform!r} devices; refusing to run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX found {len(devices)}")
    return devices[:chips]


class CompileClock:
    """Seconds spent in XLA backend compiles (JAX monitoring events)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


def serve_config(cfg, *, bucket: int = BUCKET):
    from repro.configs.base import ServeConfig
    return ServeConfig(model=cfg, split_policy="paper", prefill_mode="fused",
                       use_scheduler_metadata=True, seqlen_bucket=bucket,
                       prefill_bucket=BUCKET)


def serve(engine, reqs, clock: CompileClock, label: str):
    """Submit ``reqs``, drain, report; -> {request_id: tokens}."""
    c0, t0 = clock.seconds, time.monotonic()
    for r in reqs:
        engine.submit(r)
    outs = engine.drain()
    dt = time.monotonic() - t0
    tokens = sum(len(c.tokens) for c in outs)
    if len(outs) != len(reqs) or tokens != len(reqs) * MAX_NEW:
        raise RuntimeError(f"{label}: {len(outs)} completions with "
                           f"{tokens} tokens, expected {len(reqs)} x "
                           f"{MAX_NEW}")
    log(f"[{label}] smoke run (not a benchmark): {len(outs)} requests, "
        f"{tokens} tokens in {dt:.2f} s wall, of which "
        f"{clock.seconds - c0:.2f} s backend compile")
    log(f"[{label}] frozen decode plans (bucket -> num_splits): "
        f"{engine.planned_splits()}; prefill buckets "
        f"{engine.planned_prefill_buckets()}")
    return {c.request_id: list(c.tokens) for c in outs}


def check_close(name: str, got, want) -> None:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise RuntimeError(f"{name}: non-finite Pallas output")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    worst = err / (ATOL + RTOL * scale)
    log(f"[check] {name}: max |pallas - xla| = {err:.3e} "
        f"(max |xla| {scale:.3e}), {worst:.3f} of the bf16 bound")
    if worst > 1.0:
        raise RuntimeError(f"{name}: Pallas and XLA disagree beyond "
                           f"atol={ATOL}, rtol={RTOL}")


def queries(key, shape, k, kv_len):
    """Random bf16 queries whose scores against the resident rows of
    ``k`` have unit variance.

    The random model's cached K rows are large (its q/k projections are
    drawn with the head count as fan-in), so unit-normal queries would
    put every softmax at a near-one-hot, where an f32 rounding of one
    score moves the output by a whole V row: the comparison would then
    measure that ill-conditioning, not the kernels."""
    import jax
    import jax.numpy as jnp
    rows = jnp.arange(k.shape[1])[None, :] < kv_len[:, None]   # (B, L)
    kf = k.astype(jnp.float32)
    mean_sq = (jnp.sum(jnp.where(rows[..., None, None], kf * kf, 0.0))
               / (jnp.sum(rows) * k.shape[2] * k.shape[3]))
    return (jax.random.normal(key, shape)
            / jnp.sqrt(mean_sq)).astype(jnp.bfloat16)


def kernel_checks(cfg, kv, kv_len, plan, seed: int) -> None:
    """Pallas vs XLA on real cache rows: decode attention under the
    engine's frozen plan and under a 4-way split of the same shape, and
    causal prefill attention over slot 0."""
    import jax
    from repro.kernels import ops

    hd = cfg.resolved_head_dim
    key = jax.random.PRNGKey(seed + 1)
    plans = [plan] + ([dataclasses.replace(plan, num_splits=4)]
                      if plan.num_splits != 4 else [])
    for layer, (k, v) in kv.items():
        q = queries(jax.random.fold_in(key, layer),
                    (SLOTS, cfg.num_heads, hd), k, kv_len)
        for p in plans:
            out = {impl: jax.jit(lambda q, k, v, n, impl=impl, p=p:
                                 ops.decode_attention(q, k, v, n, plan=p,
                                                      impl=impl))(
                q, k, v, kv_len) for impl in ("xla", "pallas")}
            check_close(f"decode attention, layer {layer}, kv_len "
                        f"{[int(x) for x in kv_len]}, num_splits "
                        f"{p.num_splits}", out["pallas"], out["xla"])
    k, v = kv[CHECK_LAYERS[0]]
    n = BUCKET
    q = queries(key, (1, n, cfg.num_heads, hd), k, kv_len)
    out = {impl: jax.jit(lambda q, k, v, impl=impl:
                         ops.attention(q, k, v, causal=True, impl=impl))(
        q, k[:1, :n], v[:1, :n]) for impl in ("xla", "pallas")}
    check_close(f"causal prefill attention, layer {CHECK_LAYERS[0]}, "
                f"{n} rows", out["pallas"], out["xla"])


def one_chip(seed: int) -> None:
    import jax
    import numpy as np
    from repro.launch.serve import (build_engine, serving_config,
                                    synthetic_requests)
    from repro.models.common import param_count
    from repro.models.registry import build_model

    clock = CompileClock()
    cfg = serving_config(ARCH, full_width=True)
    log(f"[model] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} Q / {cfg.num_kv_heads} KV heads, "
        f"head_dim {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype}")
    model = build_model(cfg)
    t0 = time.monotonic()
    # one jitted program: each leaf is drawn and cast to bf16 on the
    # device without eager f32 intermediates
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"[model] {param_count(model.param_specs()) / 1e9:.2f} B "
        f"random params (seed {seed}) initialised on "
        f"{jax.devices()[0]} in {time.monotonic() - t0:.1f} s")
    reqs = synthetic_requests(cfg, NUM_REQUESTS, MAX_NEW, seed=seed,
                              prompt_len=PROMPT_LEN)
    log(f"[traffic] {len(reqs)} requests, prompt lengths "
        f"{[len(r.prompt) for r in reqs]}, {MAX_NEW} greedy new tokens "
        f"each, {SLOTS} slots, max_len {MAX_LEN}")

    streams = {}
    for impl in ("xla", "pallas"):
        icfg = cfg.replace(attention_impl=impl)
        engine = build_engine(build_model(icfg), serve_config(icfg),
                              batch_slots=SLOTS, max_len=MAX_LEN,
                              sampler="greedy")
        engine.load(params)
        streams[impl] = serve(engine, reqs, clock, impl)
        if impl == "xla":
            # the last batch's resident K/V rows, kept for the kernel
            # checks: the dense (layers, slots, max_len, H_kv, D) leaves
            layer_cache = engine._caches[0][0]
            kv = {i: (layer_cache["k"][i], layer_cache["v"][i])
                  for i in CHECK_LAYERS}
            kv_len = np.asarray(engine._pos, np.int32)
            plan = engine.sched.decode_plan(int(kv_len.max()) - 1)
        del engine
        gc.collect()

    same = sum(a == b for rid in streams["xla"]
               for a, b in zip(streams["xla"][rid], streams["pallas"][rid]))
    log(f"[agreement] greedy tokens identical in {same} of "
        f"{NUM_REQUESTS * MAX_NEW} positions (random weights: near-one-hot "
        "attention and near-tie logits let bf16 roundings diverge the "
        "streams; not a pass criterion)")
    kernel_checks(cfg, kv, jax.numpy.asarray(kv_len), plan, seed)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[memory] peak device bytes in use: "
            f"{stats['peak_bytes_in_use']}")


def memory_line(devices) -> str:
    return ", ".join(f"{d.id}: {(d.memory_stats() or {}).get('bytes_in_use')}"
                     for d in devices)


def cache_bytes_by_device(engine) -> dict:
    """KV-cache bytes each device holds, from the cache arrays' shards."""
    import jax
    out: dict = {}
    for core in getattr(engine, "cores", [engine]):
        for leaf in jax.tree.leaves(core._caches):
            for shard in leaf.addressable_shards:
                out[shard.device.id] = (out.get(shard.device.id, 0)
                                        + shard.data.nbytes)
    return dict(sorted(out.items()))


def four_chips(seed: int, devices) -> None:
    import jax
    from repro.launch.serve import (build_engine, serving_config,
                                    synthetic_requests)
    from repro.models.registry import build_model
    from repro.shard import clear_shard_plan_caches

    clock = CompileClock()
    cfg = serving_config(ARCH, full_width=True)
    model = build_model(cfg)
    # one plan bucket over the whole cache, and the split pinned to the
    # 4 chunks sp=4 realizes across chips: the three engines then run the
    # same split-KV decode math (the dp shards the identical program), so
    # their greedy tokens can be compared exactly.  Different split
    # counts group the f32 softmax sums differently, and bf16 near-ties
    # then flip greedy tokens.
    scfg = dataclasses.replace(serve_config(cfg, bucket=MAX_LEN),
                               num_splits_override=4)
    reqs = synthetic_requests(cfg, NUM_REQUESTS, MAX_NEW, seed=seed,
                              prompt_len=PROMPT_LEN)
    t0 = time.monotonic()
    # initialised on device 0, then held on the host so each topology
    # places exactly its own copies
    params = jax.device_get(
        jax.jit(model.init_params)(jax.random.PRNGKey(seed)))
    log(f"[model] {cfg.name} full width, random params (seed {seed}) "
        f"initialised on {devices[0]} in {time.monotonic() - t0:.1f} s; "
        f"{len(reqs)} requests, {MAX_NEW} greedy new tokens each")

    engine = build_engine(model, scfg, batch_slots=SLOTS, max_len=MAX_LEN,
                          sampler="greedy")
    engine.load(jax.device_put(params, devices[0]))
    want = serve(engine, reqs, clock, "one device")
    del engine
    gc.collect()

    failed = []
    for mesh, label in (("1,4", "sp=4"), ("4,1", "dp=4")):
        engine = build_engine(model, scfg, batch_slots=SLOTS,
                              max_len=MAX_LEN, sampler="greedy", mesh=mesh)
        log(f"[{label}] mesh {mesh}: bytes_in_use by device before load: "
            f"{memory_line(devices)}")
        engine.load(params)
        log(f"[{label}] mesh {mesh}: bytes_in_use by device after load: "
            f"{memory_line(devices)}")
        log(f"[{label}] KV-cache bytes by device: "
            f"{cache_bytes_by_device(engine)}")
        got = serve(engine, reqs, clock, label)
        bad = {rid: next(i for i, (a, b) in enumerate(
                   zip(got[rid], want[rid])) if a != b)
               for rid in want if got[rid] != want[rid]}
        if bad:
            failed.append(label)
            log(f"[{label}] FAIL: greedy tokens differ from the one-device "
                f"engine (request: first differing token index) {bad}")
        else:
            log(f"[{label}] greedy tokens match the one-device engine for "
                f"all {len(want)} requests")
        del engine
        clear_shard_plan_caches()
        gc.collect()
    if failed:
        raise RuntimeError(f"greedy tokens differ from the one-device "
                           f"engine under {failed}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = require_tpu(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[device] {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; compile cache {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(args.seed, devices)
    else:
        one_chip(args.seed)
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
