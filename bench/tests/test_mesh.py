"""A cell on a mesh: the harness builds the program's sequence-sharded
engine over four devices, a sound run is correct, and a run with the
exchange between chips left out is not.

Runs in a subprocess with four host devices, at a tiny size whose
prompts span several chips' shares of the cache.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from bench.tests.tiny import ENGINE, REPO, make_root

SP_TRAFFIC = {"loop": "closed", "slots": 2, "max_len": 256, "mesh": "1,4",
              "prefill_bucket": 64, "seqlen_bucket": 64, "engine": ENGINE,
              "prompt_len": {"values": [128, 192]},
              "output_len": {"median": 24, "sigma": 0.5, "min": 8,
                             "max": 48},
              "queue": 16, "pool_seed": 0}

CODE = """
import json, sys
sys.path[:0] = [{src!r}, {repo!r}]
import jax
from pathlib import Path
from bench import run as brun

def no_exchange(engine):
    # every chip combines only its own share of the cache
    jax.lax.all_gather = lambda x, axis_name, **kw: x[None]

hook = no_exchange if {fault!r} else None
out = brun.run_cell(Path({root!r}), "tiny.sp4", 2**31 + 7, 2.0, False,
                    require_chip=False, hook=hook)
print(json.dumps({{"correct": out["correct"], "check": out["check"],
                  "count": out["device"]["count"]}}))
"""


def _root(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.sp4", "config": "tiny-qwen2",
                               "traffic": "tiny-sp4", "chips": 4,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/traffic/tiny-sp4.json").write_text(json.dumps(SP_TRAFFIC))
    (root / "bench/checks/tiny.sp4.json").write_text(
        json.dumps({"sample": 2, "max_logit_gap": 0.05, "flip_share": 5.0}))
    return root


def _run(root, fault: bool):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CODE.format(src=str(REPO / "src"), repo=str(REPO),
                       root=str(root), fault=fault)
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_cell_is_correct_and_fails_without_the_exchange(tmp_path):
    root = _root(tmp_path)
    sound = _run(root, fault=False)
    assert sound["count"] == 4
    assert sound["correct"] is True, sound["check"]
    broken = _run(root, fault=True)
    assert broken["correct"] is False, broken["check"]
