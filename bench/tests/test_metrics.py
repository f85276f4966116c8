"""The trace -> metric reduction, on a hand-made trace and on a small
trace recorded on the chip (``bench/tests/data``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import math

import pytest

from bench.harness.devtrace import Event, Trace
from bench.harness.drive import ReqRecord
from bench.harness.cell import load_adapter, load_reader
from bench.run import RunData, breakdown
from bench.tests.tiny import REPO

PEAK = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
DIMS = {"d": 2048, "hq": 16, "hkv": 2, "hd": 128, "ff": 11008,
        "layers": 36, "vocab": 151936, "model_type": "qwen2",
        "adapter": load_adapter(REPO, "qwen2")}


def read(name, run):
    return load_reader(REPO, name)(run)


def hand_trace():
    """Two decode launches and one prefill in a 10 ms window on one
    device; the host steps in [0, 6] ms and waits in [6, 10] ms."""
    ms = 1e-3
    mods = [Event(0, "jit__unknown(1)", 1 * ms, 2 * ms),
            Event(0, "jit__unknown(2)", 3.5 * ms, 2 * ms),
            Event(0, "jit__unknown(1)", 7 * ms, 2 * ms)]
    ops = [Event(0, "fusion.1", 1 * ms, 1 * ms, mods[0].name),
           Event(0, "flash_decode_s1", 2 * ms, 0.5 * ms, mods[0].name),
           Event(0, "flash_combine_s1", 2.5 * ms, 0.5 * ms, mods[0].name),
           Event(0, "flash_prefill", 3.5 * ms, 1 * ms, mods[1].name),
           Event(0, "convolution.3", 4.5 * ms, 1 * ms, mods[1].name),
           Event(0, "fusion.1", 7 * ms, 2 * ms, mods[2].name)]
    host = [Event(-1, "bench.window", 0.0, 10 * ms),
            Event(-1, "bench.step", 0.0, 6 * ms),
            Event(-1, "bench.wait", 6 * ms, 4 * ms)]
    return Trace([0], ops, mods, host)


def hand_run(trace):
    # one request whose tokens 1 and 2 came from decode launches inside
    # the window, at kv_len 101 and 102
    r = ReqRecord(0, 100, 8, 0.0, token_times=[0.5, 1.0, 1.5],
                  tokens=[1, 2, 3])
    return RunData(DIMS, 1, PEAK, {0: r}, trace, (0.9, 2.0), 12.5,
                   [10.0, 20.0, 30.0], "open", (0.0, 10.0))


def test_busy_idle_and_gaps():
    t = hand_trace()
    assert t.window_s() == pytest.approx(10e-3)
    assert t.busy_s(0) == pytest.approx(6e-3)
    gaps = sorted(t.idle_gaps(0), key=lambda g: -g[1])
    assert gaps[0] == ("bench.wait", pytest.approx(1e-3)) or \
        gaps[0][0] in ("bench.wait", "bench.step")
    assert sum(g for _, g in gaps) == pytest.approx(4e-3)
    labels = {g[0] for g in gaps}
    assert labels <= {"bench.wait", "bench.step"}


def test_readers_on_a_hand_trace():
    run = hand_run(hand_trace())
    assert read("idle_share.open", run) == pytest.approx(40.0)
    assert read("decode_step_ms.closed", run) == pytest.approx(2.0)
    assert read("prefill_ms", run) == pytest.approx(2.0)
    assert read("compile_s", run) == 12.5
    assert read("queue_wait_p95_ms", run) == pytest.approx(29.0)
    # two decoded rows at kv_len 101 and 102, over 1 ms of kernels
    L, hq, hkv, hd = 36, 16, 2, 128
    kv = 101 + 102
    need = (2 * L * 2 * hq * hd * 4 + L * kv * 2 * hkv * hd * 2) / 819e9
    assert read("flash_decode_roofline", run) == pytest.approx(
        100 * need / 1e-3)
    flops = sum(36 * (2 * (2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048
                           + 3 * 2048 * 11008) + 4 * hq * hd * k)
                + 2 * 2048 * 151936 for k in (101, 102))
    assert read("decode_mfu", run) == pytest.approx(
        100 * flops / (10e-3 * 197e12))


def test_open_loop_tails_are_read_before_the_profiler_starts():
    recs = {0: ReqRecord(0, 10, 3, 0.0, token_times=[0.3, 0.4, 0.6],
                         tokens=[1, 2, 3]),
            1: ReqRecord(1, 10, 3, 0.5, token_times=[0.55, 0.65, 0.75],
                         tokens=[1, 2, 3]),
            # due after the profiler started (0.9): not read
            2: ReqRecord(2, 10, 2, 1.0, token_times=[5.0, 9.0],
                         tokens=[1, 2])}
    run = RunData(DIMS, 1, PEAK, recs, None, (0.9, 2.0), 1.0, [], "open",
                  (0.0, 10.0))
    # first tokens after 300 and 50 ms; gaps 100, 200, 100, 100 ms
    assert read("ttft_p95_ms.open", run) == pytest.approx(287.5)
    assert read("itl_p95_ms.open", run) == pytest.approx(185.0)
    # a request that never got its first token leaves no finite tail
    recs[3] = ReqRecord(3, 10, 2, 0.8)
    assert read("ttft_p95_ms.open", run) is None
    run.loop = "closed"
    assert read("ttft_p95_ms.open", run) is None
    assert read("itl_p95_ms.open", run) is None


def test_readers_find_nothing_without_a_trace():
    run = hand_run(None)
    for name in ("idle_share.closed", "decode_step_ms.open", "prefill_ms",
                 "flash_decode_roofline", "decode_mfu"):
        assert read(name, run) is None


def test_breakdown_names_programs_and_gaps():
    b = breakdown(hand_trace())
    ops = dict(b["device_ops"])
    assert ops["decode:fusion"] == pytest.approx(3e-3)
    assert ops["prefill:convolution"] == pytest.approx(1e-3)
    assert ops["prefill:flash_prefill"] == pytest.approx(1e-3)
    assert len(b["idle_gaps"]) <= 10
    assert all(not math.isnan(v) for _, v in b["idle_gaps"])


CHIP_TRACE = REPO / "bench/tests/data/chat_open_v5e.json.gz"


def chip_run():
    """The committed trace: 0.75 s of qwen2.5-3b.chat-open on one v5e
    (ten fused prefills of 512-2048 rows and two decode launches over
    32 slots), reduced by ``Trace.to_json``."""
    r = ReqRecord(0, 2000, 8, 0.0, token_times=[0.5, 1.0, 1.5],
                  tokens=[1, 2, 3])
    return RunData(DIMS, 1, PEAK, {0: r}, Trace.from_json(str(CHIP_TRACE)),
                   (0.9, 2.0), 1.0, [], "open", (0.0, 10.0))


def test_chip_trace_programs_and_times():
    run = chip_run()
    kinds = run.trace.program_kinds()
    assert sorted(set(kinds.values())) == ["decode", "prefill"]
    # the four prefill buckets of the cell take 22 / 42 / 64 / 94 ms
    assert read("prefill_ms", run) == pytest.approx(53.633, abs=1e-3)
    assert read("decode_step_ms.open", run) == pytest.approx(72.647,
                                                             abs=1e-3)
    assert read("idle_share.open", run) == pytest.approx(9.313, abs=1e-3)
    assert run.trace.busy_s(0) <= run.trace.window_s()


def test_chip_trace_breakdown_and_roofline_bound():
    run = chip_run()
    b = breakdown(run.trace)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "prefill:fusion"
    assert "decode:flash_decode_s1" in names
    assert all(label.startswith("bench.") for label, _ in b["idle_gaps"])
    # the decode kernels' roofline share stays under 100% with the most
    # live rows the two launches could hold (64 rows near 4096 cached)
    run.records[0].tokens = list(range(2 * 32 + 1))
    run.records[0].token_times = [1.0] * (2 * 32 + 1)
    run.records[0].prompt_len = 4096 - 2 * 32 - 1
    share = read("flash_decode_roofline", run)
    assert 0 < share <= 100
