"""Every cell of ``BENCHMARK.json`` resolves into its files: the
configuration, the traffic mix, a correctness limit and a reader for
each per-layer metric it reports; a cell on a mesh asks for as many
chips as its mesh spans.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json

import pytest

from bench.harness.cell import load_cell
from bench.tests.tiny import REPO

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_into_its_files(name):
    cell = load_cell(REPO, name)
    assert cell.check["max_logit_gap"] > 0 and cell.check["sample"] >= 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    mesh = cell.traffic.get("mesh")
    if mesh:
        dp, sp = (int(x) for x in mesh.split(","))
        assert dp * sp == cell.chips
        assert cell.traffic["max_len"] % sp == 0
        assert cell.traffic["slots"] % dp == 0
    else:
        assert cell.chips == 1


def test_the_sp4_cell_reports_what_its_path_can_show():
    """The sequence-sharded decode runs no Pallas decode kernel, so the
    metrics that find decode programs by that kernel are not asked of
    it."""
    cell = load_cell(REPO, "qwen2.5-3b.longctx-sp4")
    assert cell.chips == 4 and cell.traffic["mesh"] == "1,4"
    names = {m["name"] for m in cell.per_layer}
    assert {"compile_s", "decode_mfu", "idle_share.closed"} <= names
    assert not names & {"flash_decode_roofline", "decode_step_ms.closed"}
    assert {m["name"] for m in cell.end_to_end} == {
        "itl_p95_ms", "output_tok_s", "setup_s"}
