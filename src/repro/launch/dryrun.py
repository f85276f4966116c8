import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 host devices.

For every cell this script:

1. builds the right step (train_step / prefill_step / serve_step),
2. ``.lower().compile()`` on the production mesh — sharding mismatches,
   compile-time OOMs and unsupported collectives fail HERE,
3. records ``memory_analysis()`` / ``cost_analysis()`` / the collective
   schedule, and the derived roofline terms, to a JSON file under
   ``experiments/dryrun/``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
        --shape train_4k [--multi-pod] [--policy paper]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch, list_archs, shape_applicable
from repro.configs.base import (
    OptimizerConfig,
    ServeConfig,
    ShapeConfig,
    TrainConfig,
)
from repro.launch.mesh import make_production_mesh, mesh_name
from repro.models.registry import build_model
from repro.roofline.analysis import analyze, model_flops_for
from repro.roofline.probe import corrected_cost
from repro.serving.decode_step import build_mesh_decode_step, build_prefill_step
from repro.training.train_step import build_train_step

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# microbatch count for full train cells: fits the per-device activation
# footprint in HBM (see EXPERIMENTS.md §Dry-run)
TRAIN_MICROBATCHES = 4


def _lower_cell(arch: str, shape: ShapeConfig, mesh, policy: str):
    cfg = get_arch(arch)
    model = build_model(cfg)
    if shape.kind == "train":
        tcfg = TrainConfig(model=cfg, shape=shape,
                           optimizer=OptimizerConfig(),
                           microbatches=TRAIN_MICROBATCHES)
        bundle = build_train_step(model, tcfg, mesh)
        lowered = bundle.step.lower(*bundle.abstract_args())
        tokens = shape.global_batch * shape.seq_len
        kind = "train"
    elif shape.kind == "prefill":
        scfg = ServeConfig(model=cfg, shape=shape, split_policy=policy)
        bundle = build_prefill_step(model, scfg, mesh)
        lowered = bundle.step.lower(*bundle.abstract_args())
        tokens = shape.global_batch * shape.seq_len
        kind = "prefill"
    else:
        scfg = ServeConfig(model=cfg, shape=shape, split_policy=policy)
        bundle = build_mesh_decode_step(model, scfg, mesh)
        lowered = bundle.step.lower(*bundle.abstract_args())
        tokens = shape.global_batch                      # one token / seq
        kind = "decode"
    return model, bundle, lowered, tokens, kind


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             policy: str = "paper", verbose: bool = True
             ) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    cfg = get_arch(arch)
    ok, why = shape_applicable(cfg, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mname = mesh_name(mesh)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mname,
        "chips": mesh.devices.size, "policy": policy, "status": "ok",
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    t0 = time.time()
    model, bundle, lowered, tokens, kind = _lower_cell(
        arch, shape, mesh, policy)
    rec["lower_s"] = round(time.time() - t0, 2)

    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    try:
        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        if hasattr(mem, "peak_memory_in_bytes"):
            rec["memory_analysis"]["peak_memory_in_bytes"] = int(
                mem.peak_memory_in_bytes)
    except Exception as e:                                # pragma: no cover
        rec["memory_analysis"] = {"error": str(e)}

    cost = dict(compiled.cost_analysis() or {})
    rec["cost_analysis"] = {k: float(v) for k, v in cost.items()
                            if isinstance(v, (int, float))
                            and k in ("flops", "bytes accessed",
                                      "transcendentals",
                                      "utilization operand 0 {}")}

    hlo = compiled.as_text()
    mflops = model_flops_for(cfg, model.param_specs(), tokens=tokens,
                             step_kind=kind if kind == "train"
                             else "inference")
    # probe-corrected per-device cost (see roofline/probe.py: XLA counts
    # loop bodies once, so the raw full-compile numbers undercount)
    t2 = time.time()
    cc = corrected_cost(
        cfg, shape, mesh, policy=policy,
        microbatches=TRAIN_MICROBATCHES if kind == "train" else 1,
        remat=kind == "train",
        seq_split=bool(getattr(bundle, "mesh_splits", 1) > 1))
    rec["probe_s"] = round(time.time() - t2, 2)
    report = analyze(
        arch=arch, shape=shape_name, mesh_name=mname,
        chips=mesh.devices.size,
        cost={"flops": cc.flops, "bytes accessed": cc.bytes},
        hlo_text="", model_flops=mflops, step_kind=kind, policy=policy,
        note="probe-corrected")
    # collective bytes come from the probe correction, not the empty hlo
    from repro.roofline.analysis import ICI_LINK_BW
    from repro.roofline.hlo import wire_bytes
    report.per_category = {k: int(v) for k, v in cc.coll.items()}
    report.device_collective_bytes = float(wire_bytes(cc.coll))
    report.collective_s = report.device_collective_bytes / ICI_LINK_BW
    terms = {"compute": report.compute_s, "memory": report.memory_s,
             "collective": report.collective_s}
    report.dominant = max(terms, key=terms.get)
    rec["roofline"] = report.to_dict()
    rec["raw_cost_analysis_note"] = (
        "cost_analysis above is the RAW full-compile number (loop bodies "
        "counted once); roofline uses the probe-corrected values")
    if kind == "decode":
        rec["mesh_splits"] = bundle.mesh_splits
        # the frozen LaunchPlan the step was specialized on (Planner
        # output; None for attention-free families / heuristic path)
        rec["plan"] = (bundle.metadata.describe()
                       if bundle.metadata is not None else None)

    if verbose:
        ma = rec.get("memory_analysis", {})
        print(f"[{mname}] {arch} x {shape_name}: "
              f"lower {rec['lower_s']}s compile {rec['compile_s']}s | "
              f"args {ma.get('argument_size_in_bytes', 0)/2**30:.2f} GiB "
              f"temp {ma.get('temp_size_in_bytes', 0)/2**30:.2f} GiB | "
              f"dominant={report.dominant} "
              f"(c={report.compute_s*1e3:.2f}ms m={report.memory_s*1e3:.2f}ms "
              f"coll={report.collective_s*1e3:.2f}ms) "
              f"useful={report.useful_ratio:.2f}")
    return rec


def save_record(rec: Dict[str, Any], out_dir: Path = OUT_DIR) -> Path:
    d = out_dir / rec["mesh"] / rec["arch"]
    d.mkdir(parents=True, exist_ok=True)
    suffix = "" if rec.get("policy") in (None, "paper") \
        else f"-{rec['policy']}"
    p = d / f"{rec['shape']}{suffix}.json"
    p.write_text(json.dumps(rec, indent=2, default=str))
    return p


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch, shape)")
    ap.add_argument("--policy", default="paper",
                    choices=("fa3_baseline", "paper", "tpu_adaptive"))
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) \
        else [args.shape]

    failures = 0
    for arch in archs:
        for shape in shapes:
            try:
                rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                               policy=args.policy)
            except Exception as e:
                failures += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if args.multi_pod else "16x16",
                       "policy": args.policy, "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]}
                print(f"FAIL {arch} x {shape}: {rec['error']}")
            save_record(rec, Path(args.out))
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
