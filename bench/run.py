"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration x traffic mix) is looked up in ``BENCHMARK.json``
at the root of the checkout.  The program is driven in-process through
its normal entry points (``ServingEngine.submit`` / ``step``) with
weights drawn on the device from ``--seed``.  Set-up (weights, engine,
warm-up of every program the window touches, a closed loop's first
fill) is timed as ``setup_s``; then the window runs for ``--seconds``
with no compile in it.  ``--trace 1`` profiles part of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.
After the window, a sample of what was served is compared with the
plain float32 reference that the configuration names (``bench/reference``,
on the weights mapped back by the architecture's ``bench/adapters``).

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``check``: each number compared with its
limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import (Any, Callable, Dict, List, Optional,  # noqa: E402
                    Sequence, Tuple)

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TOP = 10


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """Every backend compile (or persistent-cache load) JAX records."""

    def __init__(self):
        import jax
        self.events: List[Tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.monotonic(), duration))

    def between(self, a: float, b: float) -> List[float]:
        return [d for t, d in self.events if a <= t <= b]


@dataclass
class RunData:
    """What a per-layer metric reader gets to read: ``dims`` are the
    cell's ``Cell.dims``, its architecture's sizes with its adapter
    (``bench/adapters``) under ``"adapter"``."""
    dims: Dict[str, Any]
    chips: int
    peak: Dict[str, Any]
    records: Dict[int, Any]
    trace: Any
    trace_host_window: Tuple[float, float]
    compile_setup_s: float
    queue_waits_ms: List[float]
    loop: str                           # "open" or "closed"
    window: Tuple[float, float]         # host clock: the window opens, closes


def machine_waits() -> Tuple[float, float]:
    """CPU seconds the machine's CPUs have spent so far waiting on I/O
    and stolen by the hypervisor, summed over the CPUs (``/proc/stat``;
    zeros where it cannot be read)."""
    try:
        with open("/proc/stat") as f:
            v = f.readline().split()
        hz = os.sysconf("SC_CLK_TCK")
        return int(v[5]) / hz, int(v[8]) / hz
    except (OSError, IndexError, ValueError):
        return 0.0, 0.0


class HostWatch:
    """What the process and its machine did besides the program over the
    window: the garbage collector's passes; from ``getrusage`` the
    process's major page faults, involuntary context switches and CPU
    seconds; and the machine's I/O wait and stolen CPU seconds.  A step
    that stalls for seconds is read against these."""

    def __init__(self):
        self.passes: List[float] = []
        self._t = 0.0
        self._ru = resource.getrusage(resource.RUSAGE_SELF)
        self._waits = machine_waits()
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.passes.append(time.perf_counter() - self._t)

    def stop(self) -> str:
        gc.callbacks.remove(self)
        a, b = self._ru, resource.getrusage(resource.RUSAGE_SELF)
        io, steal = (y - x for x, y in zip(self._waits, machine_waits()))
        return (f"[host] collector passes {len(self.passes)}, longest "
                f"{1e3 * max(self.passes, default=0.0):.1f} ms, total "
                f"{1e3 * sum(self.passes):.1f} ms; major page faults "
                f"{b.ru_majflt - a.ru_majflt}, involuntary context "
                f"switches {b.ru_nivcsw - a.ru_nivcsw}, CPU s user "
                f"{b.ru_utime - a.ru_utime:.2f} system "
                f"{b.ru_stime - a.ru_stime:.2f}; machine CPU s in I/O "
                f"wait {io:.2f}, stolen {steal:.2f}")


def configure_jax(root: Path) -> None:
    """The persistent compilation cache lives at a fixed path inside the
    checkout, and keeps every program, however quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found only {devices[0].platform!r} "
                     "devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def percentile(values: List[float], q: float) -> Optional[float]:
    import numpy as np
    return float(np.percentile(values, q)) if values else None


def window_gaps(records, a: float, b: float) -> List[float]:
    """Every inter-token gap whose two tokens were seen in [a, b]."""
    out = []
    for r in records.values():
        ts = r.token_times
        out += [t1 - t0 for t0, t1 in zip(ts, ts[1:]) if a <= t0 and t1 <= b]
    return out


def end_to_end(drive, setup_s: float, seconds: float) -> Dict[str, float]:
    a, b = drive.t0, drive.t_end
    recs = drive.records
    gaps = window_gaps(recs, a, b)
    due = [r for r in recs.values() if a <= r.due <= b]
    ttft = [(r.token_times[0] - r.due) if r.tokens else float("inf")
            for r in due]
    tokens = sum(1 for r in recs.values() for t in r.token_times
                 if a <= t <= b)
    out = {"setup_s": setup_s,
           "output_tok_s": tokens / seconds if tokens else None,
           "tpot_ms": 1e3 * sum(gaps) / len(gaps) if gaps else None,
           "itl_p95_ms": (1e3 * percentile(gaps, 95) if gaps else None)}
    p = percentile(ttft, 95) if ttft else None
    out["ttft_p95_ms"] = 1e3 * p if p is not None and p != float("inf") \
        else None
    return out


def breakdown(trace) -> Dict[str, List]:
    """The device ops that took most self time, named ``<program>:<op>``,
    and the longest idle gaps of the first chip by what the host did."""
    kinds = trace.program_kinds()
    per: Dict[str, float] = {}
    for e, t in trace.self_times():
        c = f"{kinds.get(e.module, 'other')}:{e.short}"
        per[c] = per.get(c, 0.0) + t
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(trace.idle_gaps(trace.devices[0]) if trace.devices
                  else [], key=lambda g: -g[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


class Tracer:
    """Profiles ``seconds`` of the window, starting ``lead`` seconds in,
    under a ``bench.window`` annotation.  The run takes the last seconds
    of its window: stopping the profiler holds the host for seconds,
    which would delay every request after it."""

    def __init__(self, lead: float, seconds: float, out_dir: str):
        self.lead, self.seconds, self.dir = lead, seconds, out_dir
        self.t_first: Optional[float] = None
        self.span: Tuple[float, float] = (0.0, 0.0)
        self._ann = None

    def __call__(self, now: float) -> None:
        import jax
        if self.t_first is None:
            self.t_first = now
        if self._ann is None and self.span[1] == 0.0 \
                and now >= self.t_first + self.lead:
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.span = (time.monotonic(), 0.0)
        elif self._ann is not None and now >= self.span[0] + self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.span = (self.span[0], time.monotonic())
        jax.profiler.stop_trace()


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             hook: Optional[Callable[[Any], None]] = None,
             keep_trace: Optional[str] = None,
             engine_overrides: Optional[Dict[str, Any]] = None,
             controls: Sequence[str] = (),
             t_start: float = T_START) -> Dict[str, Any]:
    """One run of one cell; returns the result object.  ``hook`` gets
    the engine before its weights are loaded (tests break it there);
    ``keep_trace`` names a file for the reduced device trace;
    ``engine_overrides`` replace the traffic file's engine settings;
    ``controls`` ("int8", "fp8") also judge each lower-precision
    reference put in the program's place, on the same sample, under
    ``control``."""
    from bench.harness.cell import load_cell
    cell = load_cell(root, workload)
    configure_jax(root)
    import jax
    import numpy as np
    devices = require_chips(cell.chips) if require_chip \
        else jax.devices()[:cell.chips]
    compiles = CompileLog()

    from repro.obs import ObsConfig

    from bench import peaks
    from bench.harness import check, drive, model as bmodel, traffic
    cfg, tr = cell.config, cell.traffic
    dims = cell.dims
    peak = peaks.peak(devices[0].device_kind) if trace else {}
    obs = ObsConfig(trace=True).resolve() if trace else None
    obs_epoch = time.monotonic() - obs.now_us() / 1e6 if obs else 0.0
    engine, params = bmodel.build(cell, seed, devices,
                                  overrides=engine_overrides, obs=obs,
                                  hook=hook)
    planned = traffic.generate(tr, dims["vocab"], seed, seconds)
    prompts = {p.index: p.prompt for p in planned}
    client = drive.Client(engine, planned)
    closed = tr["loop"] == "closed"
    if closed:
        warm = drive.warm_up(engine, planned, first=planned[:tr["slots"]])
        drive.fill(client, tr["slots"])
    else:
        warm = drive.warm_up(engine, planned)
    misses0 = engine.stats.misses
    # what set-up built stays alive: keep it out of the collector's full
    # passes, so that none of them pauses the window
    gc.collect()
    gc.freeze()
    t_setup_end = time.monotonic()
    setup_s = t_setup_end - t_start
    log(f"[setup] {setup_s:.3f} s: {cfg['name']} on {len(devices)} x "
        f"{devices[0].device_kind}; warm-up prompts {warm['prompts']} "
        f"(prefill buckets {warm['prefill']}, decode buckets "
        f"{warm['decode']}); {len(compiles.events)} programs compiled or "
        f"loaded, {sum(d for _, d in compiles.events):.3f} s")

    tracer = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        traced = float(tr.get("trace_seconds", 3.0))
        tracer = Tracer(max(0.0, seconds - traced), traced, tdir)
    run = drive.run_closed if closed else drive.run_open
    watch = HostWatch()
    result = run(client, seconds, on_tick=tracer)
    t_drive_end = time.monotonic()
    host = watch.stop()
    if tracer is not None:
        tracer.stop()
    in_window = compiles.between(t_setup_end, t_drive_end)
    longest = [(round(1e3 * d, 1), round(t - result.t0, 2))
               for d, t in sorted(result.stalls)[-3:]]
    log(f"[window] {seconds} s, {result.steps} engine steps; compiles in "
        f"the window: {len(in_window)} (plan-cache misses "
        f"{engine.stats.misses - misses0}); longest steps or waits "
        f"past their wake-up, ms (at s into the window): {longest}")
    log(host)
    if result.lateness:
        late = np.asarray(result.lateness) * 1e3
        log(f"[traffic] generator lateness ms: median "
            f"{np.median(late):.3f}, p95 {np.percentile(late, 95):.3f}, "
            f"max {late.max():.3f} over {len(late)} arrivals")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)

    recs = result.records
    if closed:
        attempted = [r for r in recs.values() if r.submit <= result.t_end]
        failed = []
    else:
        attempted = [r for r in recs.values()
                     if result.t0 <= r.due <= result.t_end]
        failed = [r for r in attempted if not r.tokens]

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    out: Dict[str, Any] = {"correct": False, "attempted": len(attempted),
                           "failed": len(failed)}
    if trace:
        from bench.harness.devtrace import load_xplane
        t_read = time.monotonic()
        tdata = load_xplane(tracer.dir)
        log(f"[trace] {len(tdata.ops)} device ops read in "
            f"{time.monotonic() - t_read:.1f} s")
        if keep_trace:
            from bench.harness.devtrace import summarize
            tdata.to_json(keep_trace)
            Path(keep_trace + ".txt").write_text(summarize(tracer.dir))
        shutil.rmtree(tracer.dir, ignore_errors=True)
        # an open loop's waits, of the requests due before the profiler
        # started: it slows the steps it records
        early = [] if closed else [r for r in attempted
                                   if r.due < tracer.span[0]]
        start = {}
        if obs is not None and obs.tracer is not None:
            start = {e["tid"] - 1: obs_epoch + (e["ts"] + e["dur"]) / 1e6
                     for e in obs.tracer.artifact().events
                     if e.get("name") == "queue_wait"}
        waits = [1e3 * (start[r.handle] - r.due) for r in early
                 if r.handle in start]
        data = RunData(dims, cell.chips, peak, recs, tdata, tracer.span,
                       sum(d for t, d in compiles.events
                           if t <= t_setup_end), waits, tr["loop"],
                       (result.t0, result.t_end))
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=tdata.mean_busy_s(), window_s=tdata.window_s())
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = breakdown(tdata)
    else:
        e2e = end_to_end(result, setup_s, seconds)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end
                          if e2e.get(m["name"]) is not None}
        out["device"] = device

    # the reference runs once the program's state is freed
    del client, engine, result
    gc.unfreeze()
    gc.collect()
    t_ref = time.monotonic()
    sample = check.sample(recs, int(cell.check.get("sample", 4)), seed)
    gaps = check.gaps(cell, params, prompts, sample, controls)
    log(f"[check] reference over {len(sample)} requests "
        f"({[len(r.tokens) for r in sample]} served tokens) took "
        f"{time.monotonic() - t_ref:.1f} s")
    values = check.numbers(gaps["served"])
    out["correct"], compared = check.judge(values, cell.check)
    log(f"[check] served: {values}")
    if controls:
        out["control"] = {}
        for c in controls:
            cv = check.numbers(gaps[c])
            ok, cc = check.judge(cv, cell.check)
            out["control"][c] = {"correct": ok, "check": cc,
                                 "readings": cv}
            log(f"[check] control {c}: {cv}; correct {ok}")
    out["readings"] = values
    out["check"] = compared
    for k, c in compared.items():
        log(f"[check] {k} {c['value']} limit {c['limit']}")
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: write the reduced device trace "
                         "(gzipped JSON) to this file")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace)
    except NoChip as e:
        log(f"bench: {e}; refusing to run")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
