"""The harness finds cells by name, refuses to run without a chip, and
generates the same traffic for the same seed.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from bench.harness import cell as bcell
from bench.harness import traffic
from bench.tests.tiny import REPO, TRAFFIC, make_root


def test_cell_added_as_files_only_is_found(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy", "source": "https://x.org",
                             "file": "bench/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.mix", "config": "dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["dummy.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    dummy = {"name": "dummy", "model_type": "qwen2",
             "reference": "bench/reference/qwen2.py"}
    (root / "bench/configs/dummy.json").write_text(json.dumps(dummy))
    (root / "bench/traffic/dummy-mix.json").write_text('{"loop": "open"}')
    (root / "bench/metrics/dummy_metric.py").write_text(
        "def read(run):\n    return 42.0 if run == 'x' else None\n")
    c = bcell.load_cell(root, "dummy.mix")
    assert c.config == dummy
    assert c.traffic == {"loop": "open"}
    assert "dummy_metric" in [m["name"] for m in c.per_layer]
    assert c.readers["dummy_metric"]("x") == 42.0
    assert c.readers["dummy_metric"]("y") is None
    assert "dummy_metric" not in bcell.load_cell(root, "tiny.open").readers


def test_every_cell_resolves_to_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = bcell.load_cell(REPO, w["name"])
        assert c.check["max_logit_gap"] > 0
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert len(c.end_to_end) >= 2 and c.per_layer


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "bench/run.py"), "--workload",
         "qwen2.5-3b.chat-open", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_to_run_with_only_the_benchmark_files(tmp_path):
    (tmp_path / "bench").mkdir()
    for f in ("BENCHMARK.json",):
        (tmp_path / f).write_text((REPO / f).read_text())
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2.5-3b.chat-open", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_generator_is_deterministic_per_seed():
    spec = TRAFFIC["open"]
    a = traffic.generate(spec, 512, 2**31 + 5, 10)
    b = traffic.generate(spec, 512, 2**31 + 5, 10)
    c = traffic.generate(spec, 512, 7, 10)
    assert [(p.due, p.prompt, p.max_new) for p in a] \
        == [(p.due, p.prompt, p.max_new) for p in b]
    assert [p.prompt for p in a] != [p.prompt for p in c]
    # every seed gets the same trace: sizes and arrival times, in order
    assert [(len(p.prompt), p.max_new, p.due) for p in a] \
        == [(len(p.prompt), p.max_new, p.due) for p in c]
    assert all(p.due <= q.due for p, q in zip(a, a[1:]))


def test_generator_fills_the_cache_in_a_long_context_mix():
    spec = json.loads((REPO / "bench/traffic/longctx-decode.json")
                      .read_text())
    ps = traffic.generate(spec, 151936, 3, 30)
    assert sorted({len(p.prompt) for p in ps}) == [16384, 20480, 24576,
                                                    28672]
    assert all(len(p.prompt) + p.max_new == spec["max_len"] - 1
               for p in ps)


def test_open_loop_offers_its_rate_inside_the_window():
    for rate, seconds in ((1.6, 50.0), (2.2, 40.0), (12.0, 3.0)):
        spec = dict(TRAFFIC["open"], arrivals={"rate": rate, "cv": 2.0})
        for seed in (1, 2**31 + 11):
            ps = traffic.generate(spec, 512, seed, seconds)
            due = [p.due for p in ps if p.due < seconds]
            assert len(due) == round(rate * seconds)
            assert min(p.due for p in ps if p.due >= seconds) > seconds


class _Sched:
    """The scheduler's public bucket arithmetic, as the engine has it."""

    def __init__(self, width, prefill_width, max_len):
        self.bucket_width, self.pw, self.max_len = width, prefill_width, \
            max_len

    def decode_bucket(self, t):
        return -(-min(t + 1, self.max_len) // self.bucket_width) \
            * self.bucket_width

    def prefill_len(self, n):
        return min(-(-n // self.pw) * self.pw, self.max_len)


def test_warm_up_reaches_every_bucket_the_window_can_touch():
    from bench.harness import drive
    for name in ("chat-open", "batch-decode", "longctx-decode"):
        spec = json.loads((REPO / f"bench/traffic/{name}.json").read_text())
        sched = _Sched(spec["seqlen_bucket"], spec["prefill_bucket"],
                       spec["max_len"])
        ps = traffic.generate(spec, 151936, 5, 50)
        first = ps[:spec["slots"]] if spec["loop"] == "closed" else []
        plan = drive.warm_prompts(sched, spec["max_len"], ps, first)
        want_pre = {sched.prefill_len(len(p.prompt)) for p in ps}
        want_dec = set(drive.decode_buckets(
            sched, min(len(p.prompt) for p in ps),
            min(max(len(p.prompt) + p.max_new for p in ps),
                spec["max_len"] - 1)))
        got_pre = set(plan["prefill"])
        got_dec = set(plan["decode"])
        if first:
            got_pre |= {sched.prefill_len(len(p.prompt)) for p in first}
            got_dec.add(sched.decode_bucket(max(len(p.prompt)
                                                for p in first)))
        assert got_pre >= want_pre and got_dec >= want_dec, name
        # at most one prefill bucket beyond the traffic's own
        assert len(got_pre - want_pre) <= 1, (name, got_pre - want_pre)
        assert all(n + 2 <= spec["max_len"] for n in plan["prompts"])
