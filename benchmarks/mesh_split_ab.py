"""Mesh-level policy A/B: the paper's Table 1, lifted to the pod.

For a SHORT-cache batched decode (the paper's chat regime: L_K = 512)
on the 16x16 production mesh, build the serve step under each policy and
compare the compiled programs: the mesh split decision, the collective
schedule, and the modeled per-step bound.  This is the deployment-level
consequence of the heuristic — fa3_baseline leaves the model axis
starved exactly like it left H100 SMs idle.

``--smoke`` runs the same three-policy compile-and-compare on a 4x4
mesh (16 virtual devices) with the reduced arch — seconds, CI-sized —
asserting only the mesh-independent structure (storage-forced sequence
sharding, the kernel baseline's static guard).

The benchmark always re-execs itself with ``XLA_FLAGS`` forcing the
device count (jax freezes device flags at first import, so the caller's
process — e.g. ``benchmarks.run`` — can never host it):

    PYTHONPATH=src python -m benchmarks.mesh_split_ab [--smoke]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(smoke: bool = False) -> None:
    """Re-exec under the forced device count (512 full, 16 smoke)."""
    env = dict(os.environ)
    # the forced devices are host devices: pin the child to the CPU so it
    # never reaches for an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    n = 16 if smoke else 512
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), _ROOT,
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.mesh_split_ab", "--inner"]
    if smoke:
        cmd.append("--smoke")
    subprocess.run(cmd, check=True, env=env, cwd=_ROOT)


def bench(smoke: bool = False) -> None:
    import jax  # noqa: F401  (inside the forced-device process)

    from benchmarks.common import print_table, write_csv
    from repro.configs import get_arch
    from repro.configs.base import ServeConfig, ShapeConfig
    from repro.configs.reduced import reduced_config
    from repro.launch.mesh import make_production_mesh
    from repro.compat import make_mesh
    from repro.models.registry import build_model
    from repro.plan import AttentionSpec, Planner
    from repro.roofline.analysis import HBM_BW, ICI_LINK_BW
    from repro.roofline.hlo import collective_bytes, wire_bytes
    from repro.roofline.probe import analytic_memory_bytes
    from repro.serving.decode_step import build_mesh_decode_step

    if smoke:
        mesh = make_mesh((4, 4), ("data", "model"))
        cfg = reduced_config("qwen2.5-3b", num_layers=2, d_model=64)
        batch = 8
    else:
        mesh = make_production_mesh()
        cfg = get_arch("qwen2.5-3b")
        batch = 128
    # the paper's boundary bucket, batched for serving: each data-shard
    # replica decodes with a 512-token cache; H_KV=2 (qwen2.5-3b) is the
    # Table-1 H_KV=2 row (the reduced arch keeps the GQA ratio: H_KV=1)
    shape = ShapeConfig("decode_512", 512, batch, "decode")
    model = build_model(cfg)
    axis = mesh.shape["model"]

    rows = []
    for policy in ("fa3_baseline", "paper", "tpu_adaptive"):
        scfg = ServeConfig(model=cfg, shape=shape, split_policy=policy)
        bundle = build_mesh_decode_step(model, scfg, mesh)
        compiled = bundle.step.lower(*bundle.abstract_args()).compile()
        coll = collective_bytes(compiled.as_text())
        # layer-scan body counted once -> scale by layer count
        wire = wire_bytes(coll) * cfg.num_layers
        mem = analytic_memory_bytes(cfg, shape, mesh, microbatches=1,
                                    kind="decode",
                                    seq_split=bundle.mesh_splits > 1)
        # the KERNEL-level plan for the same shape (per-chip split count)
        md = Planner(policy=policy).plan(
            AttentionSpec.decode(1, 512, cfg.num_heads, cfg.num_kv_heads,
                                 cfg.resolved_head_dim))
        rows.append([policy, bundle.mesh_splits, md.num_splits,
                     round(wire / 2**20, 1),
                     round(wire / ICI_LINK_BW * 1e3, 3),
                     round(mem / HBM_BW * 1e3, 3)])

    header = ["policy", "mesh_splits", "kernel_splits", "wire_MiB/step",
              "collective_ms", "memory_ms"]
    print_table(header, rows, "mesh + kernel policy A/B (decode, "
                f"L_K=512, H_KV={cfg.num_kv_heads}, B={batch}, "
                f"{mesh.shape['data']}x{axis} mesh"
                f"{', smoke' if smoke else ''})")
    write_csv("mesh_split_ab", header, rows, smoke=smoke)
    by = {r[0]: r for r in rows}
    # FINDING (documented in EXPERIMENTS.md): at pod scale the STORAGE
    # constraint already forces sequence-sharding for every kv < axis
    # arch — head-sharding cannot even represent the cache — so the mesh
    # decision converges across policies.  The policies still diverge at
    # the KERNEL level (the Pallas split count below), which is exactly
    # the paper's original scope.
    assert by["fa3_baseline"][1] == by["paper"][1] == axis
    assert by["fa3_baseline"][2] == 1, "kernel baseline: static guard"
    if not smoke:
        assert by["paper"][2] == 3, "kernel paper policy: boundary override"


def main(smoke: bool = False) -> None:
    """run.py entry: always a fresh forced-device process."""
    run_subprocess(smoke=smoke)


def smoke_main() -> None:
    """run.py entry for the CI-sized cell (16 devices, seconds)."""
    run_subprocess(smoke=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="4x4 mesh, reduced arch, seconds-scale")
    ap.add_argument("--inner", action="store_true",
                    help="internal: already running under forced devices")
    args = ap.parse_args()
    if args.inner:
        bench(smoke=args.smoke)
    else:
        run_subprocess(smoke=args.smoke)
