"""repro.obs: metrics registry / tracer / validate_trace units, the
Observer lifecycle on an injectable fake clock, atomic artifact writes,
deterministic engine traces, the engine's spans (both sinks, nesting,
nothing entered when disabled), stable program names, program trace
counts, the tracing-on/off bit-identity property (dense/paged x
single/dp-sharded), warning-once regressions, and (multidevice tier)
dp=2 artifact parity in an 8-device subprocess and the span table of
an sp=2 sequence-sharded engine."""
import functools
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from _hyp_compat import given, settings, strategies as st
from repro.configs.base import ServeConfig
from repro.configs.reduced import reduced_config
from repro.kernels import ops
from repro.models import build_model
from repro.obs import (
    NULL_OBSERVER,
    MetricsRegistry,
    ObsConfig,
    TraceArtifact,
    Tracer,
    atomic_write_json,
    atomic_write_text,
    plan_provenance,
    validate_trace,
)
from repro.obs import observer as observer_mod
from repro.obs.metrics import _percentile
from repro.plan import AttentionSpec, Planner
from repro.serving import Request, SamplingParams, ServingEngine
from repro.serving import engine as engine_mod
from repro.shard import ShardSpec, ShardedServingEngine, \
    clear_shard_plan_caches
from repro.spec import SpecConfig
from repro.tune.table import REFERENCE_TABLE_PATH

REPO = Path(__file__).resolve().parents[1]


class FakeClock:
    """Deterministic monotonic clock: +1 ms per reading."""

    def __init__(self, step: float = 1e-3):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduced_config("qwen2.5-3b", num_layers=2, d_model=32)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(autouse=True)
def _fresh_registry():
    clear_shard_plan_caches()
    yield
    clear_shard_plan_caches()


def _reqs(cfg, n, seed=0, max_new=4, lo=3, hi=9):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, cfg.vocab_size,
                                    size=int(rng.integers(lo, hi))).tolist(),
                    max_new_tokens=max_new) for i in range(n)]


# ---------------------------------------------------------------------------
# metrics: instruments, families, registry, prometheus exposition
# ---------------------------------------------------------------------------


def test_percentile_matches_numpy_interpolation():
    import numpy as np
    samples = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0.0, 0.25, 0.5, 0.9, 1.0):
        assert _percentile(samples, q) == pytest.approx(
            float(np.percentile(samples, 100 * q)))
    assert _percentile([], 0.5) == 0.0


def test_histogram_snapshot_counts_sum_and_cumulative_buckets():
    m = MetricsRegistry()
    h = m.histogram("lat_ms", "latency", buckets=(1.0, 10.0, 100.0))
    for x in (0.5, 5.0, 5.0, 50.0, 5000.0):
        h.observe(x)
    s = m.snapshot()["lat_ms"]
    assert s["kind"] == "histogram"
    agg = s["aggregate"]
    assert agg["count"] == 5
    assert agg["sum"] == pytest.approx(5060.5)
    assert agg["min"] == 0.5 and agg["max"] == 5000.0
    # cumulative per upper bound, +Inf catches the tail
    assert agg["buckets"] == {"1": 1, "10": 3, "100": 4, "+Inf": 5}
    assert agg["p50"] == pytest.approx(5.0)


def test_registry_memoizes_families_and_rejects_kind_mismatch():
    m = MetricsRegistry()
    assert m.counter("a", "one") is m.counter("a")
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("a")


def test_family_label_series_and_aggregate_merge():
    m = MetricsRegistry()
    c = m.counter("launches_total", "launches")
    c.inc(3, shard="0")
    c.inc(4, shard="1")
    snap = m.snapshot()["launches_total"]
    assert snap["series"] == {"shard=0": 3, "shard=1": 4}
    assert snap["aggregate"] == 7
    h = m.histogram("t_ms", "t", buckets=(10.0,))
    h.observe(1.0, shard="0")
    h.observe(100.0, shard="1")
    agg = m.snapshot()["t_ms"]["aggregate"]
    assert agg["count"] == 2 and agg["buckets"] == {"10": 1, "+Inf": 2}


def test_prometheus_text_exposition_format():
    m = MetricsRegistry()
    m.counter("tokens_total", "tokens").inc(5)
    m.histogram("ttft_ms", "ttft", buckets=(10.0, 100.0)) \
        .observe(50.0, shard="0")
    text = m.prometheus()
    assert "# HELP repro_tokens_total tokens" in text
    assert "# TYPE repro_tokens_total counter" in text
    assert "repro_tokens_total 5" in text
    assert 'repro_ttft_ms_bucket{shard="0",le="10"} 0' in text
    assert 'repro_ttft_ms_bucket{shard="0",le="100"} 1' in text
    assert 'repro_ttft_ms_bucket{shard="0",le="+Inf"} 1' in text
    assert 'repro_ttft_ms_sum{shard="0"} 50' in text
    assert 'repro_ttft_ms_count{shard="0"} 1' in text


# ---------------------------------------------------------------------------
# tracer + TraceArtifact + validate_trace (the schema gate)
# ---------------------------------------------------------------------------


def test_tracer_roundtrip_and_helpers(tmp_path):
    tr = Tracer()
    tr.ensure_process(0, "serve")
    tr.ensure_process(0, "serve")               # idempotent
    tr.ensure_thread(0, 1, "req0")
    tr.complete(0, 1, "request", "request", 10, 100, {"tokens": 3})
    tr.complete(0, 1, "admit", "request", 20, 30)
    tr.instant(0, 1, "first_token", "request", 60)
    art = tr.artifact()
    assert sum(e["ph"] == "M" for e in art.events) == 2  # proc + thread
    art.validate()
    p = tmp_path / "trace.json"
    art.save(p)
    back = TraceArtifact.load(p)
    assert back.events == art.events
    assert len(back.spans("admit")) == 1
    assert len(back.spans(cat="request")) == 2
    assert back.instants("first_token")[0]["ts"] == 60


@pytest.mark.parametrize("mutate, msg", [
    (lambda o: o.pop("traceEvents"), "traceEvents"),
    (lambda o: o["traceEvents"].append({"ph": "X"}), "missing/invalid"),
    (lambda o: o["traceEvents"].append(
        {"name": "x", "ph": "Q", "pid": 0, "tid": 0, "ts": 0}),
     "unknown ph"),
    (lambda o: o["traceEvents"].append(
        {"name": "x", "ph": "X", "pid": 0, "tid": 0, "ts": -1, "dur": 1}),
     "negative ts"),
    (lambda o: o["traceEvents"].append(
        {"name": "x", "ph": "X", "pid": 0, "tid": 0, "ts": 0}),
     "dur >= 0"),
    (lambda o: o["traceEvents"].append(
        {"name": "bogus", "ph": "M", "pid": 0, "tid": 0, "ts": 0,
         "args": {"name": "n"}}), "metadata"),
])
def test_validate_trace_rejects_schema_violations(mutate, msg):
    obj = {"traceEvents": []}
    mutate(obj)
    with pytest.raises(ValueError, match=msg):
        validate_trace(obj)


def test_validate_trace_rejects_partial_overlap_but_allows_nesting():
    def span(name, ts, dur):
        return {"name": name, "ph": "X", "pid": 0, "tid": 1,
                "ts": ts, "dur": dur}
    # proper forest: parent [0, 100), children [10, 30) and [40, 90)
    validate_trace({"traceEvents": [span("parent", 0, 100),
                                    span("a", 10, 20),
                                    span("b", 40, 50)]})
    # partial overlap: [10, 120) spills past the open parent
    with pytest.raises(ValueError, match="partially overlaps"):
        validate_trace({"traceEvents": [span("parent", 0, 100),
                                        span("bad", 10, 110)]})


# ---------------------------------------------------------------------------
# atomic artifact writes
# ---------------------------------------------------------------------------


def test_atomic_write_replaces_and_leaves_no_temp(tmp_path):
    p = tmp_path / "deep" / "stats.json"
    atomic_write_json(p, {"a": 1})
    atomic_write_json(p, {"a": 2})
    assert json.loads(p.read_text()) == {"a": 2}
    atomic_write_text(tmp_path / "m.prom", "x 1\n")
    assert (tmp_path / "m.prom").read_text() == "x 1\n"
    leftovers = [f for f in tmp_path.rglob("*.tmp")]
    assert not leftovers, f"temp files left behind: {leftovers}"


def test_atomic_write_failure_preserves_existing_file(tmp_path):
    p = tmp_path / "stats.json"
    atomic_write_json(p, {"ok": True})
    with pytest.raises(TypeError):
        atomic_write_json(p, {"bad": object()})
    assert json.loads(p.read_text()) == {"ok": True}
    assert not list(tmp_path.glob("*.tmp"))


# ---------------------------------------------------------------------------
# plan provenance + ObsConfig resolution
# ---------------------------------------------------------------------------


def test_plan_provenance_always_carries_acceptance_keys():
    plan = Planner(policy="paper").plan(
        AttentionSpec.decode(2, 256, 16, 1, 64), bucket=256)
    d = plan_provenance(("verify", 2, 256), plan)
    assert d["key"] == "verify/2/256"
    assert d["num_splits"] == plan.num_splits
    assert d["kv_dtype"] == "bfloat16"
    assert d["policy"] == "paper" and d["bucket"] == 256
    assert "mesh_splits" in d and "table_version" in d
    # fallback launches (no plan) still stamp the four keys, as nulls
    d0 = plan_provenance(None, None)
    assert d0["key"] == "fallback"
    for k in ("num_splits", "mesh_splits", "kv_dtype", "table_version"):
        assert d0[k] is None


def test_obsconfig_disabled_resolves_to_null_singleton():
    obs = ObsConfig().resolve()
    assert obs is NULL_OBSERVER and not obs.enabled
    # hooks are no-ops and never allocate observable state
    obs.on_submit(0, 0, 1)
    obs.on_launch("decode", None, None, 0)
    assert obs.metrics_snapshot() == {} and obs.prometheus() == ""
    assert obs.shard_view(3) is obs
    on = ObsConfig(trace=True).resolve()
    assert on.enabled and on.tracer is not None and on.metrics is None
    assert ObsConfig(metrics_path="x.json").resolve().metrics is not None


# ---------------------------------------------------------------------------
# Observer lifecycle on a fake clock (deterministic spans + metrics)
# ---------------------------------------------------------------------------


def test_observer_lifecycle_spans_and_metrics():
    obs = ObsConfig(trace=True, metrics=True, clock=FakeClock()).resolve()
    obs.on_submit(0, 7, 5)
    obs.on_admit_start(0)
    t0 = obs.now_us()
    obs.on_launch("prefill", ("prefill", 128), None, t0, obs.now_us())
    obs.on_admit_end(0, "full")
    obs.on_token(0, 0)
    obs.on_token(0, 1)
    obs.on_finish(0, "length")
    art = obs.tracer.artifact()
    art.validate()
    req = art.spans("request")[0]
    qw, admit = art.spans("queue_wait")[0], art.spans("admit")[0]
    # request encloses queue_wait and admit; launches stay on the
    # engine track alone (no per-rider copy on the request's track)
    for child in (qw, admit):
        assert req["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= req["ts"] + req["dur"]
    assert not art.spans(cat="step")
    assert [e["tid"] for e in art.spans(cat="launch")] == [0]
    assert req["args"] == {"request_id": 7, "prompt_len": 5,
                           "prefill": "full", "finish_reason": "length",
                           "tokens": 2}
    assert art.instants("first_token")
    launch = art.spans("prefill", cat="launch")[0]
    assert launch["tid"] == 0 and launch["args"]["key"] == "prefill/128"
    assert launch["ts"] == t0 and launch["dur"] == 1000   # 1 ms a reading
    mx = obs.metrics_snapshot()["metrics"]
    assert mx["requests_submitted_total"]["aggregate"] == 1
    assert mx["requests_finished_total"]["series"] == {"reason=length": 1}
    assert mx["tokens_total"]["aggregate"] == 2
    assert mx["ttft_ms"]["aggregate"]["count"] == 1
    assert mx["tpot_ms"]["aggregate"]["count"] == 1
    assert mx["queue_wait_ms"]["aggregate"]["count"] == 1
    # ttft (submit -> first token) strictly exceeds queue wait
    assert mx["ttft_ms"]["aggregate"]["sum"] > \
        mx["queue_wait_ms"]["aggregate"]["sum"]


def test_shard_views_share_one_clock_and_label_series():
    obs = ObsConfig(trace=True, metrics=True, clock=FakeClock()).resolve()
    v0, v1 = obs.shard_view(0), obs.shard_view(1)
    seq = [v0.now_us(), v1.now_us(), obs.now_us(), v1.now_us()]
    assert seq == sorted(seq), "shard views must merge on one timeline"
    v0.on_submit(0, 0, 3)
    v1.on_submit(0, 1, 3)
    v0.on_token(0, 0)
    v1.on_token(0, 0)
    mx = obs.metrics_snapshot()["metrics"]
    sub = mx["requests_submitted_total"]
    assert sub["series"]["shard=0"] == 1 and sub["series"]["shard=1"] == 1
    assert sub["aggregate"] == 2
    pids = {e["pid"] for e in obs.tracer.artifact().events}
    assert {0, 1} <= pids


def test_prometheus_absorbs_plan_cache_scalars():
    obs = ObsConfig(metrics=True, clock=FakeClock()).resolve()
    text = obs.prometheus({"hits": 3, "misses": 1, "policy": "paper"})
    assert "repro_plan_cache_hits 3" in text
    assert "repro_plan_cache_misses 1" in text
    assert "policy" not in text.split("repro_plan_cache_")[-1]
    sharded = obs.prometheus({
        "shards": [{"shard": 0, "hits": 2}, {"shard": 1, "hits": 5}],
        "aggregate": {"hits": 7}})
    assert 'repro_plan_cache_hits{shard="0"} 2' in sharded
    assert 'repro_plan_cache_hits{shard="1"} 5' in sharded
    assert "repro_plan_cache_hits 7" in sharded


# ---------------------------------------------------------------------------
# engine integration: deterministic traces, bit-identity, dumps
# ---------------------------------------------------------------------------


def _serve(model, scfg, reqs, *, obs=None, max_len=64, slots=2,
           sharded=False):
    if sharded:
        eng = ShardedServingEngine(
            model, scfg, spec=ShardSpec(dp=1, sp=1, slots_per_shard=slots),
            max_len=max_len, obs=obs)
    else:
        eng = ServingEngine(model, scfg, max_len=max_len,
                            batch_slots=slots, obs=obs)
    eng.load(model.init_params(jax.random.PRNGKey(0)))
    for r in reqs:
        eng.submit(r)
    return eng, eng.drain()


def test_engine_trace_is_deterministic_under_fake_clock(tiny_model):
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg, prefill_mode="fused")

    def one_run():
        obs = ObsConfig(trace=True, metrics=True,
                        clock=FakeClock()).resolve()
        eng = ServingEngine(model, scfg, max_len=64, batch_slots=2,
                            obs=obs)
        eng.load(params)
        for r in _reqs(cfg, 3, max_new=3):
            eng.submit(r)
        eng.drain()
        return obs

    a, b = one_run(), one_run()
    ea = a.tracer.artifact()
    assert ea.events == b.tracer.artifact().events, \
        "same requests + same fake clock must replay the same trace"
    ea.validate()
    assert len(ea.spans("request")) == 3
    for sp in ea.spans(cat="launch"):
        for k in ("key", "num_splits", "mesh_splits", "kv_dtype",
                  "table_version"):
            assert k in sp["args"], f"launch span missing {k}"
    assert {"prefill", "decode"} <= {sp["name"]
                                     for sp in ea.spans(cat="launch")}
    assert a.metrics_snapshot() == b.metrics_snapshot()


# ---------------------------------------------------------------------------
# engine spans: one API, two sinks (profiler + Chrome trace)
# ---------------------------------------------------------------------------


class _CountingAnnotation:
    """Stands in for a jax.profiler annotation and logs each entry."""

    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        self.log.append((type(self).__name__, self.name, self.kw))
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture
def annotations(monkeypatch):
    """Every profiler annotation the observer enters, in order."""
    log = []

    class Trace(_CountingAnnotation):
        pass

    class Step(_CountingAnnotation):
        pass

    Trace.log = Step.log = log
    monkeypatch.setattr(observer_mod, "TraceAnnotation", Trace)
    monkeypatch.setattr(observer_mod, "StepTraceAnnotation", Step)
    return log


def test_observer_span_feeds_profiler_and_trace(annotations):
    obs = ObsConfig(trace=True, clock=FakeClock()).resolve()
    with obs.span("engine.step", step_num=3):
        with obs.span("engine.decode.dispatch", bucket=256) as d:
            pass
    assert annotations == [("Step", "engine.step", {"step_num": 3}),
                           ("Trace", "engine.decode.dispatch",
                            {"bucket": 256})]
    art = obs.tracer.artifact()
    art.validate()
    disp, step = art.spans(cat="engine")
    assert (disp["name"], disp["tid"], disp["args"]) == \
        ("engine.decode.dispatch", 0, {"bucket": 256})
    assert (disp["ts"], disp["ts"] + disp["dur"]) == (d.t0, d.t1)
    assert step["args"] == {"step": 3}
    assert step["ts"] < disp["ts"] and \
        disp["ts"] + disp["dur"] < step["ts"] + step["dur"]


def _covers(outer, inner):
    return outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def _check_engine_spans(art, launches=("decode", "prefill")):
    """The span table: every engine span nests where it belongs, and
    each launch span runs from its dispatch's start to its sync's end."""
    art.validate()
    spans = art.spans(cat="engine")
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    steps = by["engine.step"]
    parents = {"engine.admit": ("engine.step",),
               "engine.prefill.dispatch": ("engine.admit",),
               "engine.prefill.sync": ("engine.admit",),
               "engine.decode.prepare": ("engine.step",),
               "engine.decode.dispatch": ("engine.step",),
               "engine.decode.sync": ("engine.step",),
               "engine.verify.prepare": ("engine.step",),
               "engine.verify.dispatch": ("engine.step",),
               "engine.verify.sync": ("engine.step",),
               "engine.emit": ("engine.step",)}
    for name, outer in parents.items():
        for e in by.get(name, []):
            assert any(_covers(o, e) for n in outer for o in by[n]), \
                f"{name} at {e['ts']} lies under no {outer}"
    for name, es in by.items():
        if name.startswith("engine.trace."):
            for e in es:
                assert any(_covers(o, e) for n in by
                           if n.endswith(".dispatch") for o in by[n]), \
                    f"{name} traced outside a dispatch"
    for e in art.spans(cat="launch"):
        kind = "prefill" if e["name"] in ("prefill", "sprefill") \
            else e["name"]
        d = [x for x in by[f"engine.{kind}.dispatch"] if x["ts"] == e["ts"]]
        s = [x for x in by[f"engine.{kind}.sync"]
             if x["ts"] + x["dur"] == e["ts"] + e["dur"]]
        assert d and s, f"launch {e['name']} at {e['ts']} off its stamps"
    assert [e["args"]["step"] for e in steps] == \
        list(range(1, len(steps) + 1))
    for kind in launches:
        assert f"engine.{kind}.dispatch" in by, sorted(by)
    return by


_REPEATS = [[5, 6, 7, 5, 6, 7, 5, 6], [1, 2, 3, 4, 1, 2, 3],
            [9, 9, 8, 9, 9, 8, 9]]


@pytest.mark.parametrize("case", ["dense", "paged", "paged_spec",
                                  "sharded"])
def test_engine_spans_nest_under_step(tiny_model, case):
    cfg, model, params = tiny_model
    obs = ObsConfig(trace=True, metrics=True, clock=FakeClock()).resolve()
    layout = "dense" if case in ("dense", "sharded") else "paged"
    scfg = ServeConfig(model=cfg, cache_layout=layout)
    spec = SpecConfig(method="ngram", k=4) if case == "paged_spec" \
        else None
    reqs = [Request(i, p, max_new_tokens=16,
                    sampling=SamplingParams(speculation=spec))
            for i, p in enumerate(_REPEATS)]
    eng, _ = _serve(model, scfg, reqs, obs=obs, max_len=96,
                    sharded=case == "sharded")
    art = obs.tracer.artifact()
    by = _check_engine_spans(
        art, ("prefill", "verify") if spec else ("prefill", "decode"))
    assert len(by["engine.admit"]) == len(reqs)
    assert {e["args"]["request_id"] for e in by["engine.admit"]} == \
        {0, 1, 2}
    assert {"engine.trace.prefill"} <= set(by)
    if case == "sharded":
        assert {e["pid"] for e in art.spans(cat="engine")} == {0}


def test_disabled_observer_enters_no_trace_annotation(tiny_model,
                                                      annotations):
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg)
    _serve(model, scfg, _reqs(cfg, 3, max_new=3))
    assert annotations == []
    obs = ObsConfig(trace=True, clock=FakeClock()).resolve()
    _serve(model, scfg, _reqs(cfg, 3, max_new=3), obs=obs)
    entered = {name for _, name, _ in annotations}
    assert {"engine.step", "engine.admit", "engine.decode.dispatch",
            "engine.trace.decode"} <= entered
    assert annotations[0][0] == "Step"


@pytest.fixture
def named_programs(monkeypatch):
    """name -> (jitted step, impl, bound statics, abstract args of its
    first call), for every program the engine builds by name."""
    seen = {}
    real = engine_mod._named_jit

    def spy(impl, name, **static):
        fn = real(impl, name, **static)

        def call(*args):
            seen.setdefault(name, (fn, impl, static, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)))
            return fn(*args)
        return call

    monkeypatch.setattr(engine_mod, "_named_jit", spy)
    return seen


_NAME_RE = {"decode": r"decode_b\d+_s\d+", "prefill": r"prefill_b\d+",
            "verify": r"verify_k\d+_b\d+_s\d+",
            "sprefill": r"sprefill_b\d+_q\d+"}


@pytest.mark.parametrize("case", ["dense", "paged_shared_spec"])
def test_engine_programs_lower_under_stable_names(tiny_model, case,
                                                  named_programs):
    """Each engine program lowers to the module ``jit_<name>``, and to
    the same program text as the anonymous partial it replaced."""
    cfg, model, params = tiny_model
    if case == "dense":
        scfg, spec, want = ServeConfig(model=cfg), None, \
            {"decode", "prefill"}
        prompts = _REPEATS
    else:
        scfg = ServeConfig(model=cfg, cache_layout="paged",
                           cache_page_size=8, prefill_bucket=16,
                           seqlen_bucket=16, share_prefix=True)
        spec, want = SpecConfig(method="ngram", k=3), \
            {"prefill", "sprefill", "verify"}
        base = [4, 5, 6, 7] * 4
        prompts = [base + [1, 2], base + [3, 3, 2]]
    eng = ServingEngine(model, scfg, max_len=96, batch_slots=2)
    eng.load(params)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=16,
                           sampling=SamplingParams(speculation=spec)))
        eng.step()
    eng.drain()
    kinds = set()
    for name, (fn, impl, static, args) in named_programs.items():
        kind = name.split("_", 1)[0]
        assert re.fullmatch(_NAME_RE[kind], name), name
        kinds.add(kind)
        text = fn.lower(*args).as_text()
        assert text.startswith(f"module @jit_{name} ")
        anon = jax.jit(functools.partial(impl, **static),
                       donate_argnums=(1,)).lower(*args).as_text()
        assert "_unknown" not in text
        assert text == anon.replace("jit__unknown", f"jit_{name}")
    assert want <= kinds, kinds


def test_program_traces_total_counts_each_program_once(tiny_model):
    cfg, model, params = tiny_model
    obs = ObsConfig(metrics=True, clock=FakeClock()).resolve()
    eng = ServingEngine(model, ServeConfig(model=cfg), max_len=64,
                        batch_slots=2, obs=obs)
    eng.load(params)

    def traces():
        return obs.metrics_snapshot()["metrics"][
            "program_traces_total"]["series"]

    for r in _reqs(cfg, 3, max_new=3):
        eng.submit(r)
    eng.drain()
    warm = traces()
    programs = len(eng.sched.plans)
    assert sum(warm.values()) == programs >= 2
    assert set(warm) == {"kind=decode", "kind=prefill"}
    for r in _reqs(cfg, 3, seed=1, max_new=3):
        eng.submit(r)
    eng.drain()
    assert len(eng.sched.plans) == programs
    assert traces() == warm, "a warm program was traced again"


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_decode_traces_name_the_cache_update_path(tiny_model, layout):
    """Each decode program's trace says which path its step updates the
    KV cache on: a one-device engine writes every layer in place."""
    cfg, model, params = tiny_model
    obs = ObsConfig(trace=True, metrics=True, clock=FakeClock()).resolve()
    _serve(model, ServeConfig(model=cfg, cache_layout=layout),
           _reqs(cfg, 3, max_new=3), obs=obs)
    metrics = obs.metrics_snapshot()["metrics"]
    decodes = metrics["program_traces_total"]["series"]["kind=decode"]
    assert metrics["decode_program_traces_total"]["series"] == \
        {"cache_update=in_place": decodes}
    spans = [e for e in obs.tracer.artifact().spans(cat="engine")
             if e["name"] == "engine.trace.decode"]
    assert len(spans) == decodes >= 1
    assert {e["args"]["inplace_layers"] for e in spans} == \
        {cfg.num_layers}


@settings(max_examples=4, deadline=None)
@given(layout=st.sampled_from(["dense", "paged"]),
       sharded=st.sampled_from([False, True]),
       seed=st.integers(0, 3))
def test_property_tracing_on_off_bit_identical(tiny_model, layout,
                                               sharded, seed):
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg, cache_layout=layout)
    reqs = _reqs(cfg, 4, seed=seed, max_new=4)

    clear_shard_plan_caches()
    ops.reset_policy_eval_count()
    eng_off, outs_off = _serve(model, scfg, reqs, sharded=sharded)
    evals_off = ops.policy_eval_count()

    clear_shard_plan_caches()
    ops.reset_policy_eval_count()
    obs = ObsConfig(trace=True, metrics=True, clock=FakeClock()).resolve()
    eng_on, outs_on = _serve(model, scfg, reqs, obs=obs, sharded=sharded)
    evals_on = ops.policy_eval_count()

    assert [c.tokens for c in outs_off] == [c.tokens for c in outs_on], \
        "tracing changed the greedy token stream"
    assert [c.finish_reason for c in outs_off] == \
        [c.finish_reason for c in outs_on]
    if sharded:
        stats_off = [c.stats.to_json() for c in eng_off.cores]
        stats_on = [c.stats.to_json() for c in eng_on.cores]
    else:
        stats_off, stats_on = eng_off.stats.to_json(), eng_on.stats.to_json()
    assert stats_off == stats_on, "tracing changed PlanCacheStats"
    assert evals_off == evals_on == 0, "policy ran inside a traced step"
    obs.tracer.artifact().validate()


def test_engine_owned_dump_writes_both_artifacts(tiny_model, tmp_path):
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg,
                       stats_path=str(tmp_path / "stats.json"),
                       trace_path=str(tmp_path / "trace.json"),
                       metrics_path=str(tmp_path / "metrics.prom"))
    _serve(model, scfg, _reqs(cfg, 2, max_new=3))
    stats = json.loads((tmp_path / "stats.json").read_text())
    assert stats["policy"] == "paper"
    trace = json.loads((tmp_path / "trace.json").read_text())
    validate_trace(trace)
    prom = (tmp_path / "metrics.prom").read_text()
    # .prom suffix selects text exposition, with plan-cache scalars
    assert "# TYPE repro_ttft_ms histogram" in prom
    assert "repro_plan_cache_total_launches" in prom
    assert not list(tmp_path.glob("*.tmp"))


def test_sharded_dump_merges_shards_onto_one_artifact(tiny_model,
                                                      tmp_path):
    cfg, model, params = tiny_model
    scfg = ServeConfig(model=cfg,
                       stats_path=str(tmp_path / "stats.json"),
                       trace_path=str(tmp_path / "trace.json"),
                       metrics_path=str(tmp_path / "metrics.json"))
    _serve(model, scfg, _reqs(cfg, 3, max_new=3), sharded=True)
    trace = json.loads((tmp_path / "trace.json").read_text())
    validate_trace(trace)
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "shard0" in names
    snap = json.loads((tmp_path / "metrics.json").read_text())
    assert "shard=0" in snap["metrics"]["ttft_ms"]["series"]
    # plan_cache section rides the merge_stats_snapshots path
    pc = snap["plan_cache"]
    assert [s["shard"] for s in pc["shards"]] == [0]
    assert pc["aggregate"]["total_launches"] == \
        pc["shards"][0]["total_launches"]
    stats = json.loads((tmp_path / "stats.json").read_text())
    assert set(stats) >= {"topology", "shards", "aggregate"}


# ---------------------------------------------------------------------------
# structured warning events (exactly once, python warning kept for compat)
# ---------------------------------------------------------------------------


def test_len_capacity_warning_fires_exactly_once(tiny_model):
    cfg, model, params = tiny_model
    obs = ObsConfig(trace=True, metrics=True, clock=FakeClock()).resolve()
    # both requests decode into the max_len wall; the python warning and
    # the structured event must each fire exactly once per engine
    reqs = [Request(i, [7, 8, 9], max_new_tokens=64) for i in range(2)]
    with pytest.warns(RuntimeWarning, match="KV cache capacity"):
        _, outs = _serve(model, ServeConfig(model=cfg), reqs, obs=obs,
                         max_len=16)
    assert all(c.finish_reason == "cache_capacity" for c in outs)
    warn = obs.metrics_snapshot()["metrics"]["engine_warnings_total"]
    assert warn["series"] == {"code=len_capacity": 1}
    assert len(obs.tracer.artifact().instants("warning:len_capacity")) == 1


def test_registry_fallback_warning_fires_exactly_once(tiny_model,
                                                      tmp_path):
    cfg, model, params = tiny_model
    for name, backend, device in (("a_tpu.json", "tpu", "TPU v5e"),
                                  ("b_gpu.json", "gpu", "H100")):
        d = json.loads(REFERENCE_TABLE_PATH.read_text())
        d["fingerprint"]["backend"] = backend
        d["fingerprint"]["device"] = device
        (tmp_path / name).write_text(json.dumps(d))
    obs = ObsConfig(trace=True, metrics=True, clock=FakeClock()).resolve()
    with pytest.warns(RuntimeWarning, match="no table in registry"):
        eng = ServingEngine(
            model, ServeConfig(model=cfg, split_policy="measured",
                               tune_table_path=str(tmp_path)),
            max_len=64, batch_slots=1, obs=obs)
    assert eng.stats.table_registry_fallbacks == 1
    warn = obs.metrics_snapshot()["metrics"]["engine_warnings_total"]
    assert warn["series"] == {"code=table_registry_fallback": 1}
    assert len(obs.tracer.artifact()
               .instants("warning:table_registry_fallback")) == 1


# ---------------------------------------------------------------------------
# multidevice tier: dp=2 artifact parity in an 8-device subprocess
# ---------------------------------------------------------------------------


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.multidevice
def test_dp2_trace_merges_both_shards_bit_identical_tokens(tmp_path):
    out = run_py(f"""
    import json
    import jax, numpy as np
    from repro.configs.base import ServeConfig
    from repro.configs.reduced import reduced_config
    from repro.models import build_model
    from repro.obs import validate_trace
    from repro.serving import Request
    from repro.shard import ShardSpec, ShardedServingEngine

    cfg = reduced_config("qwen2.5-3b", num_layers=2, d_model=32)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    def reqs():
        rng = np.random.default_rng(0)
        return [Request(i, rng.integers(1, 250,
                        size=int(rng.integers(2, 8))).tolist(),
                        max_new_tokens=5) for i in range(6)]

    tdir = {str(tmp_path)!r}
    spec = ShardSpec(dp=2, sp=1, slots_per_shard=2)
    on = ShardedServingEngine(
        model, ServeConfig(model=cfg,
                           trace_path=tdir + "/trace.json",
                           metrics_path=tdir + "/metrics.json"),
        spec=spec, max_len=64)
    on.load(params)
    for r in reqs():
        on.submit(r)
    outs_on = on.drain()

    from repro.shard import clear_shard_plan_caches
    clear_shard_plan_caches()
    off = ShardedServingEngine(model, ServeConfig(model=cfg),
                               spec=spec, max_len=64)
    off.load(params)
    for r in reqs():
        off.submit(r)
    outs_off = off.drain()
    assert [c.tokens for c in outs_on] == [c.tokens for c in outs_off], \\
        "tracing changed sharded greedy tokens"

    trace = json.load(open(tdir + "/trace.json"))
    validate_trace(trace)
    pids = {{e["pid"] for e in trace["traceEvents"]}}
    assert pids == {{0, 1}}, pids
    snap = json.load(open(tdir + "/metrics.json"))
    series = snap["metrics"]["requests_submitted_total"]["series"]
    assert series.get("shard=0", 0) + series.get("shard=1", 0) == 6
    assert [s["shard"] for s in snap["plan_cache"]["shards"]] == [0, 1]
    print("OK dp2 obs parity")
    """)
    assert "OK dp2 obs parity" in out


@pytest.mark.multidevice
def test_sp2_engine_spans_nest_on_the_sharded_path():
    out = run_py("""
    import jax
    from repro.configs.base import ServeConfig
    from repro.configs.reduced import reduced_config
    from repro.models import build_model
    from repro.obs import ObsConfig, validate_trace
    from repro.serving import Request
    from repro.shard import ShardSpec, ShardedServingEngine

    cfg = reduced_config("qwen2.5-3b", num_layers=2, d_model=32)
    model = build_model(cfg)
    obs = ObsConfig(trace=True, metrics=True).resolve()
    eng = ShardedServingEngine(
        model, ServeConfig(model=cfg),
        spec=ShardSpec(dp=1, sp=2, slots_per_shard=2), max_len=64,
        obs=obs)
    eng.load(model.init_params(jax.random.PRNGKey(0)))
    for i in range(3):
        eng.submit(Request(i, [3 + i, 4, 5, 6, 7], max_new_tokens=4))
    eng.drain()
    art = obs.tracer.artifact()
    validate_trace(art.to_json())
    names = sorted({e["name"] for e in art.spans(cat="engine")})
    launch = {e["args"]["mesh_splits"] for e in art.spans("decode")}
    series = obs.metrics_snapshot()["metrics"]["program_traces_total"]
    paths = obs.metrics_snapshot()["metrics"][
        "decode_program_traces_total"]["series"]
    layers = {e["args"]["inplace_layers"]
              for e in art.spans(cat="engine")
              if e["name"] == "engine.trace.decode"}
    print("SPANS", names, launch, sorted(series["series"]))
    print("PATHS", sorted(paths), layers)
    """, devices=2)
    line = [ln for ln in out.splitlines() if ln.startswith("SPANS")][0]
    for name in ("engine.step", "engine.admit", "engine.prefill.dispatch",
                 "engine.prefill.sync", "engine.decode.prepare",
                 "engine.decode.dispatch", "engine.decode.sync",
                 "engine.emit", "engine.trace.decode",
                 "engine.trace.prefill"):
        assert repr(name) in line, line
    assert "{2}" in line, line
    assert "'kind=decode,shard=0'" in line or "kind=decode" in line, line
    # the sequence-sharded decode writes its cache inside its shard_map
    paths = [ln for ln in out.splitlines() if ln.startswith("PATHS")][0]
    assert paths == "PATHS ['cache_update=scan,shard=0'] {0}", paths
