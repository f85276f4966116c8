"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run on the CPU at a tiny size (the
chip check skipped) with one fault planted in the engine, and sees
``correct`` false; the sound run beside them sees it true.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from bench import run as brun
from bench.tests.tiny import make_root

SECONDS = 3.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("faults"))


def _run(root, workload, hook=None):
    return brun.run_cell(root, workload, 2**31 + 99, SECONDS, False,
                         require_chip=False, hook=hook)


def stale_cache(engine):
    """Every decode step returns the cache it was given, unchanged."""
    def build(plan):
        step = jax.jit(functools.partial(engine._decode_impl, plan=plan))

        def run(params, caches, *args):
            tok, _ = step(params, caches, *args)
            return tok, caches
        return run
    engine._build_decode = build


def altered_token(engine):
    """The sampler's token is altered where it is produced."""
    sample = engine.sampler.sample
    vocab = engine.cfg.vocab_size

    def wrong(logits, state, pos):
        return (sample(logits, state, pos) + 1) % vocab
    engine.sampler.sample = wrong


def half_batch(engine):
    """A decode step computes the first half of the slots and repeats
    their tokens for the rest."""
    impl = engine._decode_impl

    def halved(params, caches, token, t, state, plan=None):
        tok, caches = impl(params, caches, token, t, state, plan=plan)
        h = tok.shape[0] // 2
        return jnp.concatenate([tok[:h], tok[:tok.shape[0] - h]]), caches
    engine._decode_impl = halved


@pytest.mark.parametrize("workload", ["tiny.open", "tiny.closed"])
def test_sound_run_is_correct(root, workload):
    out = _run(root, workload)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", [stale_cache, altered_token, half_batch])
@pytest.mark.parametrize("workload", ["tiny.open", "tiny.closed"])
def test_fault_is_not_correct(root, workload, fault):
    out = _run(root, workload, hook=fault)
    assert out["correct"] is False, out["check"]
