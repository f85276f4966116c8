"""Run every benchmark (one per paper table/figure + the roofline report).

    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (
        cache_ab,
        mesh_split_ab,
        metadata_ab,
        prefix_ab,
        quant_ab,
        regression_sweep,
        roofline_report,
        serving_ab,
        shard_ab,
        spec_ab,
        table1_ab,
        tune_ab,
        u_curve_sweep,
    )

    jobs = [
        ("table1_ab (paper Table 1)", table1_ab.main),
        ("u_curve_sweep (paper Fig. 3)", u_curve_sweep.main),
        ("regression_sweep (paper §5.3, 160 configs)",
         regression_sweep.main),
        ("roofline_report (§Roofline)", roofline_report.main),
        ("metadata_ab (paper §5 serving path)", metadata_ab.main),
        ("serving_ab (fused vs loop prefill admission, TTFT/TPOT)",
         serving_ab.main),
        ("cache_ab (DenseLayout vs PagedKVCache, mixed prompt lengths)",
         cache_ab.main),
        ("prefix_ab (share_prefix on vs off, shared system prompt)",
         prefix_ab.main),
        ("tune_ab (measured vs paper vs fa3_baseline split policies)",
         tune_ab.main),
        ("spec_ab (speculative verify steps vs plain decode)",
         spec_ab.main),
        ("quant_ab (fused quantized KV vs dequant-then-attend)",
         quant_ab.main),
        ("shard_ab (single vs dp slot shards vs sp seq-sharded decode; "
         "re-execs under 8 forced devices)", shard_ab.main),
        ("mesh_split_ab smoke (pod policy A/B; re-execs under 16 "
         "forced devices — full 512-device run stays manual)",
         mesh_split_ab.smoke_main),
    ]
    failures = 0
    for name, fn in jobs:
        print(f"\n{'=' * 72}\nBENCH {name}\n{'=' * 72}")
        t0 = time.time()
        try:
            fn()
            print(f"[ok] {name} ({time.time() - t0:.1f}s)")
        except Exception as e:                           # pragma: no cover
            failures += 1
            print(f"[FAIL] {name}: {type(e).__name__}: {e}")
    print(f"\n{len(jobs) - failures}/{len(jobs)} benchmarks ok")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
