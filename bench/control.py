"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--controls int8,fp8] [--override kv_quant=int8]

Runs the cell once per seed in one process, as ``bench/run.py`` would
(a window of ``--seconds`` at the cell's own load), and prints for each
seed one JSON line: the numbers the check compares for what the program
served (``served``: the lower readings) and, for each control in
``--controls``, the same numbers for the tokens that the reference with
its weight matrices rounded to that type puts first on the same prompts
and served tokens, judged by the harness's own check (``control``).
With ``--override`` the engine runs with that setting replaced, so that
``served`` is the program's own lower-precision path: with
``kv_quant=int8`` its int8 KV cache.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--override", action="append", default=[])
    args = ap.parse_args(argv)
    from bench.run import NoChip, run_cell
    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        overrides[k] = json.loads(v) if v[:1] in "0123456789[{tfn" else v
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = run_cell(ROOT, args.workload, seed, args.seconds, False,
                           engine_overrides=overrides or None,
                           controls=controls, t_start=time.monotonic())
        except NoChip as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "overrides": overrides,
                          "served": {"correct": out["correct"],
                                     "check": out["check"],
                                     "readings": out["readings"]},
                          "control": out.get("control", {}),
                          "attempted": out["attempted"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
