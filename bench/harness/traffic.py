"""The one traffic generator: a traffic file's parameters -> requests.

Every seed gets the same trace: the same prompt lengths, output lengths
and arrival times, in the same order, all drawn once from the file's
``pool_seed``.  The seed draws the tokens of each prompt (and, in the
harness, the weights).  Runs with different seeds then do the same work
under the same bursts.  A seed that reordered the lengths would change
the work in the window: with 32 slots near full, a burst that met the
long outputs filled every slot and held later requests in the queue.

Lengths come from ``{"median", "sigma", "min", "max"}`` (lognormal,
clipped), ``{"values": [...]}`` (cycled), or for outputs ``{"fill":
true}`` (up to the cache's last row).  An open loop's arrivals are
Gamma-distributed gaps with the file's coefficient of variation ``cv``,
scaled so that exactly ``rate`` x ``seconds`` requests (rounded) fall
due inside the window: the offered load in the window is the file's
``rate`` (req/s) whatever the bursts drew, and the gaps after it keep
that scale.  A closed loop has no arrivals, its requests queue in order.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


@dataclass
class Planned:
    index: int
    due: float                 # seconds after the window opens (open loop)
    prompt: List[int]
    max_new: int


def _lengths(spec: Dict[str, Any], n: int, rng) -> np.ndarray:
    if "values" in spec:
        vals = np.asarray(spec["values"], np.int64)
        return np.resize(vals, n)
    mu = math.log(spec["median"])
    raw = rng.lognormal(mu, spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def pool_size(traffic: Dict[str, Any], seconds: float) -> int:
    """Requests a run may draw: an open loop's arrivals over the window
    plus the minute it may wait after it; a closed loop's queue."""
    if traffic["loop"] == "open":
        rate = traffic["arrivals"]["rate"]
        return int(math.ceil(rate * (seconds + 60))) + 16
    return int(traffic["queue"])


def window_arrivals(traffic: Dict[str, Any], seconds: float) -> int:
    """Requests an open loop offers inside a window of ``seconds``."""
    return max(1, int(round(traffic["arrivals"]["rate"] * seconds)))


def generate(traffic: Dict[str, Any], vocab: int, seed: int,
             seconds: float) -> List[Planned]:
    n = pool_size(traffic, seconds)
    pool = np.random.default_rng(traffic["pool_seed"])
    prompt = _lengths(traffic["prompt_len"], n, pool)
    out_spec = traffic["output_len"]
    if out_spec.get("fill"):
        out = traffic["max_len"] - 1 - prompt
    else:
        out = _lengths(out_spec, n, pool)
    due = np.zeros(n)
    if traffic["loop"] == "open":
        arr = traffic["arrivals"]
        gaps = pool.gamma(1.0 / arr["cv"] ** 2, 1.0, size=n)
        k = window_arrivals(traffic, seconds)
        # arrivals 0..k-1 fall due in the window, arrival k half a gap
        # after it closes
        gaps *= seconds / (gaps[:k - 1].sum() + gaps[k - 1] / 2)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    rng = np.random.default_rng(int(seed) % (1 << 64))
    tokens = rng.integers(0, vocab, size=int(prompt.sum()))
    starts = np.concatenate([[0], np.cumsum(prompt)])
    return [Planned(i, float(due[i]),
                    tokens[starts[i]:starts[i + 1]].tolist(), int(out[i]))
            for i in range(n)]
