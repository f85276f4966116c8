"""Mesh-native serving A/B: single engine vs dp slot shards vs sp
sequence-sharded decode (repro.shard).

Three cells serve the SAME greedy request stream on the reduced
qwen2.5-3b (H_KV < 4, the paper's low-head-count regime — sp=4 is
storage-forced, so ``mesh_splits`` provenance is guaranteed):

- ``single``  — one ServingEngine, 2 slots (the baseline).
- ``dp4``     — 4 data-parallel slot shards x 2 slots = 8 slots (4x the
  capacity claim), each shard admitting against its OWN page budget.
- ``sp4``     — one shard whose decode sequence-shards the KV cache
  over 4 chips (the fused shard_map split-KV combine — chips for SMs).

Structural claims (the reproducible part):
- greedy tokens bit-identical across all three cells, per request_id;
- zero policy evaluations inside traced code in every cell;
- every sp4 decode plan carries ``mesh_splits == 4`` and the realized
  shard mesh;
- dp4 launches are counted PER SHARD and every shard worked.

The benchmark needs 8 virtual devices, so it always re-execs itself in
a fresh process with ``XLA_FLAGS`` set (jax device flags are frozen at
first import):

    PYTHONPATH=src python -m benchmarks.shard_ab [--smoke]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(smoke: bool = False) -> None:
    """Re-exec the benchmark under 8 forced host devices."""
    env = dict(os.environ)
    # the forced devices are host devices: pin the child to the CPU so it
    # never reaches for an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), _ROOT,
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.shard_ab", "--inner"]
    if smoke:
        cmd.append("--smoke")
    subprocess.run(cmd, check=True, env=env, cwd=_ROOT)


def bench(smoke: bool = False) -> None:
    import time

    import jax
    import numpy as np

    from benchmarks.common import print_table, write_csv
    from repro.configs.base import ServeConfig
    from repro.configs.reduced import reduced_config
    from repro.kernels import ops
    from repro.models.registry import build_model
    from repro.obs import ObsConfig
    from repro.serving import Request, ServingEngine
    from repro.shard import (
        ShardSpec,
        ShardedServingEngine,
        clear_shard_plan_caches,
    )

    assert len(jax.devices()) >= 8, \
        "shard_ab needs 8 devices (run via the --inner re-exec)"

    cfg = reduced_config("qwen2.5-3b", num_layers=2,
                         d_model=32 if smoke else 64)
    assert cfg.num_kv_heads < 4, \
        "A/B needs the low-head-count shape (sp=4 storage-forced)"
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    scfg = ServeConfig(model=cfg)
    max_len = 256
    n_req = 8 if smoke else 24
    max_new = 8 if smoke else 24
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(4, 16))).tolist(),
                    max_new_tokens=max_new)
            for i in range(n_req)]

    cells = [
        ("single", 1, 1),
        ("dp4", 4, 1),
        ("sp4", 1, 4),
    ]
    header = ["mode", "dp", "sp", "slots", "requests", "new_tokens",
              "wall_s", "toks_per_s", "launches", "per_shard_launches",
              "ttft_ms_mean", "tpot_ms_mean", "policy_evals"]
    rows, token_sets, shard_launches = [], [], {}
    for mode, dp, sp in cells:
        clear_shard_plan_caches()
        ops.reset_policy_eval_count()
        # TTFT/TPOT read off the engine's repro.obs metrics registry
        # (shard cells label every series per shard, merged in the
        # family aggregate) — no hand-timing around drain()
        obs = ObsConfig(metrics=True).resolve()
        if mode == "single":
            eng = ServingEngine(model, scfg, max_len=max_len,
                                batch_slots=2, obs=obs)
        else:
            eng = ShardedServingEngine(
                model, scfg,
                spec=ShardSpec(dp=dp, sp=sp, slots_per_shard=2),
                max_len=max_len, obs=obs)
        eng.load(params)
        t0 = time.monotonic()
        for r in reqs:
            eng.submit(r)
        outs = eng.drain()
        wall = time.monotonic() - t0
        toks = {c.request_id: c.tokens for c in outs}
        total = sum(len(t) for t in toks.values())
        if mode == "single":
            launches = eng.stats.total_launches
            per_shard = [launches]
            slots = eng.B
        else:
            per_shard = [c.stats.total_launches for c in eng.cores]
            launches = sum(per_shard)
            slots = eng.B
            shard_launches[mode] = per_shard
            if sp > 1:
                plans = {k: e.plan for k, e in
                         eng.cores[0].sched.plans.items()
                         if isinstance(k, int)}
                assert plans and all(
                    p.mesh_splits == sp and p.seq_shard_mesh is not None
                    for p in plans.values()), \
                    "sp decode plans must carry the realized mesh split"
        evals = ops.policy_eval_count()
        mx = obs.metrics_snapshot()["metrics"]
        ttft = mx["ttft_ms"]["aggregate"]
        tpot = mx["tpot_ms"]["aggregate"]
        assert ttft["count"] == len(outs), \
            "every request must have stamped a first token"
        if mode != "single":
            shard_labels = {k for k in mx["ttft_ms"]["series"] if k}
            assert shard_labels == {f"shard={d}" for d in range(dp)}, \
                "sharded cells must label TTFT series per shard"
        token_sets.append(toks)
        rows.append([mode, dp, sp, slots, len(outs), total,
                     round(wall, 2), round(total / max(wall, 1e-9), 1),
                     launches, "/".join(str(x) for x in per_shard),
                     round(ttft["mean"], 1), round(tpot["mean"], 1),
                     evals])

    title = ("mesh-native serving A/B: single vs dp=4 slots vs sp=4 "
             f"seq-sharded decode ({'smoke' if smoke else 'full'}, "
             "8 virtual devices)")
    print_table(header, rows, title)
    write_csv("shard_ab", header, rows, smoke=smoke)

    # structural claims
    assert rows[1][3] == 4 * rows[0][3], \
        "dp=4 must serve 4x the single engine's slots"
    assert all(t == token_sets[0] for t in token_sets), \
        "shard topology changed greedy tokens"
    assert all(r[12] == 0 for r in rows), \
        "policy ran inside a traced step"
    assert all(n > 0 for n in shard_launches["dp4"]), \
        "every dp shard must have admitted + launched work"
    print(f"\nshard A/B: {n_req} requests bit-identical across all "
          f"topologies, dp4 slots = 4x single, per-shard launches "
          f"{shard_launches['dp4']}, sp4 plans carry mesh_splits=4, "
          "policy evals 0")


def main(smoke: bool = False) -> None:
    """run.py entry: always a fresh 8-device process."""
    run_subprocess(smoke=smoke)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale cell sizes (make shard-smoke)")
    ap.add_argument("--inner", action="store_true",
                    help="internal: already running under forced devices")
    args = ap.parse_args()
    if args.inner:
        bench(smoke=args.smoke)
    else:
        run_subprocess(smoke=args.smoke)
