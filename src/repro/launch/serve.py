"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Random-inits a model and drives the request-lifecycle
:class:`~repro.serving.ServingEngine` (submit/step/stream/drain) over a
synthetic request stream, printing per-request completions and aggregate
TPOT.  The model is reduced (``--d-model``/``--layers``, CPU-sized) unless
``--full-width`` serves the registered config at its published widths;
``--impl pallas`` serves through the Pallas kernels.  ``--policy`` A/Bs
the paper's heuristic against the flawed baseline on the same requests;
``--temperature/--top-k/--top-p`` select seeded sampling (default
greedy); ``--stream`` prints TOKEN/FINISHED events as the engine emits
them; ``--prefill`` switches between fused bucketed admission and the
legacy teacher-forcing loop.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Tuple

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs.base import ModelConfig, ServeConfig
from repro.configs.reduced import reduced_config
from repro.core.split_policy import available_policies
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serving import (
    FINISHED,
    TOKEN,
    Request,
    SamplingParams,
    ServingEngine,
    get_sampler,
)


def serving_config(arch: str, *, full_width: bool = False,
                   d_model: int = 128, num_layers: int = 2,
                   attention_impl: str = "xla") -> ModelConfig:
    """The served model config: the registered one at its published
    widths with ``full_width``, else ``reduced_config`` at ``d_model`` /
    ``num_layers`` (CPU-sized)."""
    cfg = get_arch(arch)
    if not full_width:
        cfg = reduced_config(cfg, num_layers=num_layers, d_model=d_model)
    if cfg.family in ("vlm", "encdec"):
        raise NotImplementedError(
            "CLI serving drives text-only archs; frontend-stub archs are "
            "exercised by the tests")
    return cfg.replace(attention_impl=attention_impl)


def build_engine(model, scfg: ServeConfig, *, batch_slots: int,
                 max_len: int, sampler: str = "categorical", mesh=None):
    """The launcher's engine: one :class:`ServingEngine`, or with
    ``mesh`` ("dp,sp") a :class:`~repro.shard.ShardedServingEngine` whose
    ``batch_slots`` are slots PER SHARD."""
    if mesh:
        from repro.shard import ShardedServingEngine, ShardSpec
        spec = ShardSpec.parse(mesh, slots_per_shard=batch_slots)
        return ShardedServingEngine(model, scfg, spec=spec, max_len=max_len,
                                    sampler=get_sampler(sampler))
    return ServingEngine(model, scfg, max_len=max_len,
                         batch_slots=batch_slots,
                         sampler=get_sampler(sampler))


def synthetic_requests(cfg: ModelConfig, num_requests: int, max_new: int, *,
                       seed: int = 0, prompt_len: Tuple[int, int] = (4, 12),
                       shared_prefix: int = 0, temperature: float = 0.0,
                       top_k: int = 0, top_p: float = 1.0) -> List[Request]:
    """Seeded random-token requests: a ``shared_prefix``-token "system
    prompt" common to all of them, then a unique tail of
    ``prompt_len[0] <= n < prompt_len[1]`` tokens."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, size=shared_prefix).tolist() \
        if shared_prefix else []
    return [
        Request(i, system
                + rng.integers(0, cfg.vocab_size,
                               size=rng.integers(*prompt_len)).tolist(),
                max_new_tokens=max_new,
                sampling=SamplingParams(temperature=temperature,
                                        top_k=top_k, top_p=top_p,
                                        seed=seed + i))
        for i in range(num_requests)]


def run_serving(arch: str, *, num_requests: int = 8, max_new: int = 16,
                policy: str = "paper", batch_slots: int = 4,
                max_len: int = 256, d_model: int = 128,
                num_layers: int = 2, full_width: bool = False,
                attention_impl: str = "xla", seed: int = 0,
                num_splits_override=None, temperature: float = 0.0,
                top_k: int = 0, top_p: float = 1.0,
                sampler: str = "categorical",
                prefill_mode: str = "auto", stream: bool = False,
                cache_layout: str = "dense", share_prefix: bool = False,
                speculate=None, speculate_k: int = 4,
                speculate_max_rejects=None, kv_quant=None,
                tune_table=None, stats_path=None, mesh=None,
                trace_path=None, metrics_path=None, log_fn=print):
    cfg = serving_config(arch, full_width=full_width, d_model=d_model,
                         num_layers=num_layers,
                         attention_impl=attention_impl)
    model = build_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    scfg = ServeConfig(model=cfg, split_policy=policy,
                       num_splits_override=num_splits_override,
                       prefill_mode=prefill_mode,
                       cache_layout=cache_layout,
                       share_prefix=share_prefix,
                       speculation=speculate,
                       speculation_k=speculate_k,
                       speculation_max_rejects=speculate_max_rejects,
                       kv_quant=kv_quant,
                       tune_table_path=(str(tune_table) if tune_table
                                        else None),
                       stats_path=(str(stats_path) if stats_path
                                   else None),
                       trace_path=(str(trace_path) if trace_path
                                   else None),
                       metrics_path=(str(metrics_path) if metrics_path
                                     else None),
                       shard=mesh)
    engine = build_engine(model, scfg, batch_slots=batch_slots,
                          max_len=max_len, sampler=sampler, mesh=mesh)
    engine.load(params)

    # --share-prefix traffic models the production shape the knob
    # exists for: every request opens with the same "system prompt"
    # (long enough to span full pages), then a short unique tail
    reqs = synthetic_requests(cfg, num_requests, max_new, seed=seed,
                              shared_prefix=96 if share_prefix else 0,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p)
    t0 = time.monotonic()
    handles = [engine.submit(r) for r in reqs]
    if stream:
        while engine.has_work():
            for ev in engine.step():
                if ev.kind == TOKEN:
                    log_fn(f"req {ev.request_id} token[{ev.index}] = "
                           f"{ev.token}")
                elif ev.kind == FINISHED:
                    log_fn(f"req {ev.request_id} finished "
                           f"({ev.finish_reason})")
    outs = engine.drain()
    dt = time.monotonic() - t0
    total_new = sum(len(c.tokens) for c in outs)
    for c in outs:
        log_fn(f"req {c.request_id}: prompt {len(c.prompt)} toks -> "
               f"{c.tokens[:8]}{'...' if len(c.tokens) > 8 else ''}")
    log_fn(f"policy={policy}: {len(outs)} requests, {total_new} tokens "
           f"in {dt:.2f}s ({1e3 * dt / max(1, total_new):.1f} ms/token)")
    log_fn("frozen plans (bucket -> num_splits): "
           f"{engine.planned_splits()}")
    if mesh:
        spec_d = engine.spec.describe()
        log_fn(f"shard topology dp={spec_d['dp']} x sp={spec_d['sp']} "
               f"({spec_d['total_slots']} slots over "
               f"{spec_d['num_devices']} devices, "
               f"{engine.plan.fingerprint})")
        for row in engine.describe():
            budget = (f", pages {row['free_pages']}/"
                      f"{row['total_pages']} free"
                      if "total_pages" in row else "")
            log_fn(f"  shard {row['shard']}: {row['routed']} requests "
                   f"over {row['slots']} slots, {row['launches']} "
                   f"launches{budget}")
    if kv_quant:
        log_fn(f"kv quant: {kv_quant} storage + f32 scales "
               f"(plans keyed on the {kv_quant} family, "
               f"dtype_bytes={engine.sched.decode_spec(128).workload().dtype_bytes})")
    if engine.tune_table is not None:
        st = engine.stats
        log_fn(f"measured policy: table {engine.tune_table.version}, "
               f"{st.measured_lookups} lookups, "
               f"{st.measured_fallbacks} fallbacks to "
               f"'{engine.tune_table.fallback_policy}'")
    if stats_path:
        log_fn(f"plan-cache stats snapshot: {stats_path}")
    if trace_path:
        log_fn(f"request-lifecycle trace (load at https://ui.perfetto.dev"
               f"): {trace_path}")
    if metrics_path:
        log_fn(f"serving metrics snapshot: {metrics_path}")
    if cache_layout == "paged":
        cs = engine.cache_stats()
        log_fn(f"paged cache: {cs['total_pages']} pages of "
               f"{cs['page_size']} ({cs['storage_bytes']} B vs dense "
               f"{cs['dense_bytes']} B), {cs['free_pages']} free")
        if share_prefix:
            log_fn(f"prefix sharing: {cs['prefix_hits']} hits, "
                   f"{cs['prefix_shared_rows']} prompt rows reused, "
                   f"{cs['pages_allocated_total']} pages allocated, "
                   f"{cs['prefix_copies']} page copies, "
                   f"{cs['prefix_anchored_pages']} anchored")
    if engine.prefill_mode == "fused":
        log_fn("fused prefill buckets: "
               f"{engine.planned_prefill_buckets()}")
    if speculate:
        st = engine.stats
        log_fn(f"speculation ({speculate}, k={speculate_k}): "
               f"{st.spec_steps} verify steps, acceptance "
               f"{st.spec_acceptance_rate:.2f} "
               f"({st.spec_accepted}/{st.spec_proposed} drafts), "
               f"{st.spec_tokens_per_step:.2f} tokens/step, "
               f"{st.spec_disabled} requests disabled; verify plans "
               f"{engine.sched.planned_verify_keys()}")
    assert len(handles) == len(outs)
    return outs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--policy", default="paper",
                    choices=available_policies())
    ap.add_argument("--tune-table", default=None,
                    help="calibrated repro.tune SplitTable JSON for "
                         "--policy measured (write one with `python -m "
                         "repro.launch.tune`)")
    ap.add_argument("--stats-path", default=None,
                    help="dump PlanCacheStats.to_json() here at drain")
    ap.add_argument("--trace", default=None, dest="trace_path",
                    help="repro.obs: dump the Chrome trace-event JSON "
                         "serving timeline here at drain (load it at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics", default=None, dest="metrics_path",
                    help="repro.obs: dump the serving metrics snapshot "
                         "here at drain (.prom/.txt suffix selects "
                         "Prometheus text exposition, else JSON)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--full-width", action="store_true",
                    help="serve the registered config at its published "
                         "widths (default: reduced to d_model 128, 2 "
                         "layers, CPU-sized)")
    ap.add_argument("--impl", default="xla", choices=("xla", "pallas"),
                    help="attention impl: pure-jnp XLA or the Pallas "
                         "kernels (compiled on a TPU, interpreted on CPU)")
    ap.add_argument("--splits", type=int, default=None,
                    help="explicit num_splits override: the engine's "
                         "Planner bypasses the policy (FA3's explicit "
                         "num_splits argument)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation (1.0 = off)")
    ap.add_argument("--sampler", default="categorical",
                    help="sampler registry name (greedy | categorical; "
                         "extensible via repro.serving.register_sampler)")
    ap.add_argument("--prefill", default="auto",
                    choices=("auto", "fused", "loop"),
                    help="admission path: fused bucketed prefill vs the "
                         "legacy teacher-forcing loop")
    ap.add_argument("--cache-layout", default="dense",
                    choices=["dense", "paged"],
                    help="repro.cache storage layout (paged: resident-"
                         "bucket views + page-budget admission)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="share identical prompt prefixes across "
                         "requests (refcounted copy-on-write pages; "
                         "requires --cache-layout paged)")
    ap.add_argument("--speculate", default=None,
                    help="speculative decoding: drafter registry name "
                         "(ngram | prompt_lookup; extensible via "
                         "repro.spec.register_drafter)")
    ap.add_argument("--speculate-k", type=int, default=4,
                    help="draft tokens per verify step (with --speculate)")
    ap.add_argument("--speculate-max-rejects", type=int, default=None,
                    help="consecutive zero-accept verify steps before a "
                         "request stops speculating (default: never)")
    ap.add_argument("--kv-quant", default=None,
                    choices=["int8", "fp8"],
                    help="repro.quant low-precision KV serving mode: "
                         "quantize-on-write KV cache + in-kernel dequant "
                         "on pallas, quant-keyed split plans everywhere")
    ap.add_argument("--mesh", default=None,
                    help="mesh-native topology 'dp,sp' (repro.shard): "
                         "dp data-parallel slot shards x sp sequence-"
                         "shard chips per shard; --slots becomes slots "
                         "PER SHARD.  Needs dp*sp devices (CPU: set "
                         "XLA_FLAGS=--xla_force_host_platform_"
                         "device_count)")
    ap.add_argument("--stream", action="store_true",
                    help="print TOKEN/FINISHED events as they happen")
    args = ap.parse_args()
    enable_compile_cache()
    run_serving(args.arch, num_requests=args.requests,
                max_new=args.max_new, policy=args.policy,
                batch_slots=args.slots, full_width=args.full_width,
                attention_impl=args.impl,
                num_splits_override=args.splits,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, sampler=args.sampler,
                prefill_mode=args.prefill, stream=args.stream,
                cache_layout=args.cache_layout,
                share_prefix=args.share_prefix,
                speculate=args.speculate,
                speculate_k=args.speculate_k,
                speculate_max_rejects=args.speculate_max_rejects,
                kv_quant=args.kv_quant,
                tune_table=args.tune_table, stats_path=args.stats_path,
                mesh=args.mesh, trace_path=args.trace_path,
                metrics_path=args.metrics_path)


if __name__ == "__main__":
    main()
