"""An architecture comes as files: the harness finds its adapter by the
configuration's ``model_type`` and its reference by the configuration's
``reference`` key.  Qwen2's adapter gives the weights, the reference
gaps and the metric counts that the harness gave before they moved
there (the constants below were read from the harness before the move).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import hashlib
import json
import re

import jax
import numpy as np
import pytest

from bench import peaks, run as brun
from bench.harness import check
from bench.harness.cell import load_cell
from bench.harness.drive import ReqRecord
from bench.metrics import _lib
from bench.tests.tiny import CONFIG, REPO, make_root

# Qwen2's block without the q/k/v biases, as a new architecture would
# come: a configuration, an adapter and a reference, all new files
ADAPTER = '''"""Qwen2's block without q/k/v biases: Qwen2's adapter, biases
off."""
import dataclasses
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "qwen2_without_biases", Path(__file__).with_name("qwen2.py"))
_q = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_q)
BIASES = ("q_bias", "k_bias", "v_bias")
_shapes, _paths = _q._canonical_shapes, _q._program_paths
_q._canonical_shapes = lambda n, tied: {
    k: v for k, v in _shapes(n, tied).items() if k not in BIASES}
_q._program_paths = lambda n: {
    k: v for k, v in _paths(n).items() if k not in BIASES}
dims, make_params = _q.dims, _q.make_params
canonical_layer, canonical_globals = _q.canonical_layer, _q.canonical_globals
layer_matmul_params = _q.layer_matmul_params
decode_token_flops = _q.decode_token_flops
flash_decode_row = _q.flash_decode_row


def model_config(cfg, engine):
    return dataclasses.replace(_q.model_config(cfg, engine), qkv_bias=False)
'''
REFERENCE = '''"""Qwen2's reference with no q/k/v biases: each layer's biases
are 0."""
import jax.numpy as jnp

from bench.reference import qwen2 as _q

gaps_at, firsts_at, round_weights = _q.gaps_at, _q.firsts_at, _q.round_weights


def _zero_biases(w):
    return dict(w, **{b: jnp.zeros(w[p].shape[1], jnp.float32) for b, p in
                      (("q_bias", "q_proj"), ("k_bias", "k_proj"),
                       ("v_bias", "v_proj"))})


def hidden(layers, globals_, cfg, seq, lower=""):
    return _q.hidden((_zero_biases(w) for w in layers), globals_, cfg, seq,
                     lower=lower)
'''
NOBIAS = dict(CONFIG, name="tiny-nobias", model_type="qwen2_nobias",
              reference="bench/reference/qwen2_nobias.py")
# sound runs of the tiny no-bias cell read max_logit_gap 0 - 0.108 over
# twelve seeds on the CPU; a stale cache, an altered token or half the
# batch left out read 3.4 - 6.1
NOBIAS_LIMIT = 0.3
PEAK = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


def _add_architecture(root):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-nobias", "source": CONFIG["source"],
                             "file": "bench/configs/tiny-nobias.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-nobias.closed",
                               "config": "tiny-nobias",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny-nobias.json").write_text(json.dumps(NOBIAS))
    (root / "bench/adapters/qwen2_nobias.py").write_text(ADAPTER)
    (root / "bench/reference/qwen2_nobias.py").write_text(REFERENCE)
    (root / "bench/checks/tiny-nobias.closed.json").write_text(
        json.dumps({"sample": 3, "max_logit_gap": NOBIAS_LIMIT}))


def _leaf_names(params):
    return {jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("trace", [False, True])
def test_an_architecture_added_as_files_runs_correct(tmp_path, monkeypatch,
                                                     trace):
    root = make_root(tmp_path)
    _add_architecture(root)
    # bench/ of the checkout is the repo's with files added, none edited
    for f in (REPO / "bench").rglob("*"):
        rel = f.relative_to(REPO)
        if f.is_file() and not {"tests", "__pycache__"} & set(rel.parts):
            assert (root / rel).read_bytes() == f.read_bytes(), rel
    cell = load_cell(root, "tiny-nobias.closed")
    assert cell.adapter.__file__ == str(root / "bench/adapters"
                                        / "qwen2_nobias.py")
    biased = _leaf_names(load_cell(root, "tiny.closed").adapter.make_params(
        CONFIG, 1))
    names = _leaf_names(cell.adapter.make_params(cell.config, 1))
    bias = re.compile(r"\['b[qkv]'\]$")
    assert {n for n in biased if bias.search(n)} and \
        not {n for n in names if bias.search(n)}
    assert not {"q_bias", "k_bias", "v_bias"} & set(
        cell.adapter.canonical_layer(
            cell.adapter.make_params(cell.config, 1), cell.config, 0))
    # a traced run counts its per-layer metrics through the checkout's
    # adapter; the CPU has no published peaks, so it takes the v5e's
    monkeypatch.setattr(peaks, "peak", lambda kind: PEAK)
    out = brun.run_cell(root, "tiny-nobias.closed", 2**31 + 5, 2.0, trace,
                        require_chip=False)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0
    if trace:
        assert out["metrics"]["decode_mfu"]["value"] > 0


@pytest.mark.parametrize("key, value", [
    ("model_type", "nonesuch"), ("reference", "bench/reference/nonesuch.py")])
def test_a_missing_adapter_or_reference_is_named(tmp_path, key, value):
    root = make_root(tmp_path)
    (root / "bench/configs/tiny-qwen2.json").write_text(
        json.dumps(dict(CONFIG, **{key: value})))
    missing = root / ("bench/adapters/nonesuch.py" if key == "model_type"
                      else value)
    with pytest.raises(FileNotFoundError, match=re.escape(str(missing))):
        load_cell(root, "tiny.closed")


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    return load_cell(make_root(tmp_path_factory.mktemp("qwen2")),
                     "tiny.closed")


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed, digest", [
    (1, "ef8a020e968b4068125489dab3853f4e900c967abd46588958200ee5398a26ab"),
    (2**31 + 99,
     "582212ffc11702e756016257b76ec1d7f32b929279414cac3f77e9466774850e")])
def test_qwen2_weights_are_as_before(tiny_cell, seed, digest):
    assert _digest(tiny_cell.adapter.make_params(tiny_cell.config, seed)) \
        == digest


GAPS = {
    "served": [[1.429219126701355, 3.5798892974853516, 2.8627874851226807,
                4.448026657104492, 3.303072690963745, 4.297330856323242,
                0.5479099750518799, 3.0632505416870117, 3.4316933155059814],
               [3.5306396484375, 2.9435465335845947, 1.7933939695358276,
                4.197460174560547, 3.1998343467712402]],
    "int8": [[0.0, 0.05791640281677246, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
             [0.0, 0.0045506954193115234, 0.0, 0.0, 0.0]],
    "fp8": [[0.0, 0.05791640281677246, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
            [0.0687403678894043, 0.0, 0.0, 0.0, 0.0]],
}


def test_qwen2_reference_gaps_are_as_before(tiny_cell):
    rng = np.random.default_rng(0)
    prompts = {0: rng.integers(0, 512, 37).tolist(),
               1: rng.integers(0, 512, 70).tolist()}
    reqs = [ReqRecord(0, 37, 9, 0.0, tokens=rng.integers(0, 512, 9).tolist()),
            ReqRecord(1, 70, 5, 0.0, tokens=rng.integers(0, 512, 5).tolist())]
    params = tiny_cell.adapter.make_params(tiny_cell.config, 3)
    got = check.gaps(tiny_cell, params, prompts, reqs, ("int8", "fp8"))
    assert set(got) == set(GAPS)
    for k, want in GAPS.items():
        for g, w in zip(got[k], want, strict=True):
            np.testing.assert_array_equal(g, np.asarray(w, np.float32))


KV = [1, 17, 4096, 16384, 28672, 32768]
# config -> a cell of it, and its layer's matmul parameters, decode FLOPs
# a token at each of KV, and decode-attention seconds for KV's rows
COUNTS = {
    "qwen2.5-3b.longctx-decode": (
        77070336, [6171688960, 6176407552, 7379353600, 11003232256,
                   14627110912, 15835070464], 0.0036924314725274724),
    "qwen2.5-1.5b.batch-decode": (
        46792704, [3087310848, 3090063360, 3791781888, 5905711104,
                   8019640320, 8724283392], 0.0028710509401709402),
}


@pytest.mark.parametrize("workload", sorted(COUNTS))
def test_qwen2_metric_counts_are_as_before(workload):
    params, flops, need = COUNTS[workload]
    cell = load_cell(REPO, workload)
    n = cell.dims
    assert _lib.layer_matmul_params(n) == params
    assert [_lib.decode_token_flops(n, k) for k in KV] == flops
    assert _lib.flash_decode_need(n, KV, PEAK) == need

