"""The control of the correctness check, at a size a test run can hold.

The control is the reference with its weight matrices rounded to int8,
put in the program's place (``bench/control.py`` reads it on the chip at
each cell's own size).  At this tiny size its logits move too little to
flip a greedy token, so the test checks the machinery: the rounding is
int8 with one scale per output column, and a run reads the control on
the same sample as the sound reading.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as brun
from bench.harness.cell import load_cell
from bench.tests.tiny import REPO, make_root


@pytest.fixture(scope="module")
def reference():
    """The reference the harness loads for qwen2.5-3b's cells."""
    return load_cell(REPO, "qwen2.5-3b.longctx-decode").reference


def _matrix():
    return jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * \
        jnp.linspace(0.1, 3.0, 32)[None, :]


def test_int8_rounding_has_one_scale_per_output_column(reference):
    w = _matrix()
    q = reference.round_weights(w, "int8")
    scale = np.abs(np.asarray(w)).max(axis=0) / 127.0
    steps = np.asarray(q) / scale[None, :]
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert np.abs(steps).max() <= 127 + 1e-3
    assert (np.abs(np.asarray(q - w)) <= scale[None, :] / 2 + 1e-6).all()
    assert not np.array_equal(np.asarray(q), np.asarray(w))


def test_fp8_rounding_keeps_three_mantissa_bits_per_scaled_column(
        reference):
    w = _matrix()
    q = np.asarray(reference.round_weights(w, "fp8"))
    scale = np.abs(np.asarray(w)).max(axis=0) / 448.0
    x = q / scale[None, :]
    assert np.abs(x).max() <= 448 * (1 + 1e-5)
    # e4m3: a normal value is 1.mmm x 2^e, so x / 2^floor(log2|x|) has
    # at most three fractional bits
    big = np.abs(x) >= 2.0 ** -6
    frac = np.abs(x[big]) / 2.0 ** np.floor(np.log2(np.abs(x[big])))
    np.testing.assert_allclose(frac * 8, np.round(frac * 8), atol=1e-3)
    rel = np.abs(q - np.asarray(w))[big] / np.abs(np.asarray(w))[big]
    assert rel.max() <= 2.0 ** -4 + 1e-6 and rel.max() > 2.0 ** -8


def test_a_run_judges_each_control_on_its_own_sample(tmp_path):
    root = make_root(tmp_path)
    out = brun.run_cell(root, "tiny.closed", 5, 2.0, False,
                        require_chip=False, controls=("int8", "fp8"))
    assert out["correct"] is True
    for c in ("int8", "fp8"):
        ctl = out["control"][c]
        assert set(ctl["check"]) == set(out["check"])
        v = ctl["readings"]["max_logit_gap"]
        assert np.isfinite(v) and v >= 0.0
        print(c, ctl["readings"], out["readings"])
    assert list(out)[-1] == "check"


def test_judge_compares_each_number_that_has_a_limit():
    from bench.harness import check
    v = check.numbers([np.array([0.0, 0.02, 0.0]), np.array([0.0, 0.0])])
    assert v["max_logit_gap"] == 0.02 and v["flip_share"] == 20.0
    assert check.judge(v, {"max_logit_gap": 0.05}) == (
        True, {"max_logit_gap": {"value": 0.02, "limit": 0.05}})
    ok, cmp = check.judge(v, {"max_logit_gap": 0.05, "flip_share": 10.0})
    assert not ok and set(cmp) == {"max_logit_gap", "flip_share"}
    assert check.judge(v, {"sample": 4})[0] is False      # nothing compared
    assert check.judge({}, {"max_logit_gap": 0.05})[0] is False
