"""Find an open-loop cell's knee once, by a sweep of fixed rates on the chip.

    python3 bench/sweep.py --workload qwen2.5-3b.chat-open \
        --rates 2,3,4,5 --seconds 30 --seed 1

One process builds the cell's engine as ``bench/run.py`` does
(``bench.harness.model.build``) and warms it up, then offers each rate
in turn for ``--seconds`` (arrivals as the traffic file draws them, at
that rate) and drains the engine between rates.  For each rate it
prints the backlog (requests submitted and not yet admitted) averaged
over the first and the last third of the window, and the p95 time to
first token of the requests due in the window (one still without a
token at the close counts as infinitely late).  The knee is the highest rate whose
backlog does not grow across the window; the cell's traffic file then
states a fixed rate below it.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import numpy as np
    from bench.harness import drive, model as bmodel, traffic
    from bench.harness.cell import load_cell
    from bench.run import configure_jax, require_chips

    cell = load_cell(ROOT, args.workload)
    configure_jax(ROOT)
    devices = require_chips(cell.chips)
    engine, _ = bmodel.build(cell, args.seed, devices)
    tr = dict(cell.traffic)
    vocab = cell.dims["vocab"]
    for rate in (float(r) for r in args.rates.split(",")):
        tr["arrivals"] = dict(tr["arrivals"], rate=rate)
        planned = traffic.generate(tr, vocab, args.seed, args.seconds)
        drive.warm_up(engine, planned)
        backlog = []
        d = drive.Client(engine, planned)
        res = drive.run_open(
            d, args.seconds, wait_after=0.0,
            on_tick=lambda now: backlog.append(
                (now, engine.sched.queue_depth())))
        engine.drain()
        t = np.asarray([b[0] for b in backlog]) - res.t0
        q = np.asarray([b[1] for b in backlog], float)
        third = args.seconds / 3
        due = [r for r in res.records.values() if r.due <= res.t_end]
        ttft = [r.token_times[0] - r.due if r.tokens else float("inf")
                for r in due]
        print(json.dumps({
            "rate": rate, "arrivals": len(due),
            "unserved_at_close": sum(1 for r in due if not r.tokens),
            "backlog_first_third": float(q[t < third].mean()),
            "backlog_last_third": float(q[(t >= 2 * third)
                                         & (t < args.seconds)].mean()),
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
            "tokens_per_s": sum(len(r.tokens) for r in
                                res.records.values()) / args.seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
