"""Whether what the timed path served is correct.

After the window, a sample of the served requests, drawn from the seed
and always holding the one with the most served tokens, is replayed
through the plain float32 reference that the cell's configuration names
(``cell.reference``, on weights mapped back by ``cell.adapter``),
teacher-forced on each prompt and its served tokens.  Each served
(greedy) token gets its gap: how far its logit lies below the
reference's best logit of its row.  Two numbers are
read from the gaps of the whole sample: the widest (``max_logit_gap``)
and the share of tokens that are not the reference's first
(``flip_share``, in %).  The cell's limits are in
``bench/checks/<workload>.json``; a number without a limit there is
printed and not compared.

A control puts a lower-precision reference in the program's place: at
each position of the same prompts and served tokens, the token that the
reference with its weight matrices rounded to int8 or fp8 puts first,
with its gap under the float32 reference.  The benchmark's own runs
never read it.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.harness.drive import ReqRecord

NUMBERS = ("max_logit_gap", "flip_share")


def sample(records: Dict[int, ReqRecord], k: int, seed: int
           ) -> List[ReqRecord]:
    """``k`` requests drawn from the seed: finished ones where there
    are any, else those in flight; the one that served most is always
    in."""
    served = [r for r in records.values() if r.tokens]
    done = [r for r in served if r.finished]
    pool = sorted(done or served, key=lambda r: r.index)
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.tokens), -r.index))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps(cell, params, prompts: Dict[int, List[int]],
         reqs: List[ReqRecord], controls: Sequence[str] = ()
         ) -> Dict[str, List[np.ndarray]]:
    """Per request, the gap of every served token (``"served"``) and of
    every token each control in ``controls`` puts first (keyed by the
    control's type), under ``cell``'s reference."""
    cfg, adapter, ref = cell.config, cell.adapter, cell.reference
    L = adapter.dims(cfg)["layers"]
    glob = adapter.canonical_globals(params, cfg)

    def layers():
        return (adapter.canonical_layer(params, cfg, i) for i in range(L))

    out: Dict[str, List[np.ndarray]] = {"served": []}
    for c in controls:
        out[c] = []
    for r in reqs:
        n = len(prompts[r.index])
        seq = list(prompts[r.index]) + list(r.tokens[:-1])
        h = ref.hidden(layers(), glob, cfg, seq)
        out["served"].append(ref.gaps_at(h, glob, cfg, n, r.tokens))
        for c in controls:
            hc = ref.hidden(layers(), glob, cfg, seq, lower=c)
            picks = ref.firsts_at(hc, glob, cfg, n, len(r.tokens))
            del hc
            out[c].append(ref.gaps_at(h, glob, cfg, n, picks))
        del h
    return out


def numbers(per_request: List[np.ndarray]) -> Dict[str, float]:
    if not per_request:
        return {}
    g = np.concatenate(per_request)
    return {"max_logit_gap": float(g.max()),
            "flip_share": float(100.0 * np.mean(g > 0))}


def judge(values: Dict[str, float], limits: Dict
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Whether every number with a limit lies within it, and each such
    number beside its limit.  No number, or no limit, is not correct."""
    compared = {k: {"value": values.get(k), "limit": limits[k]}
                for k in NUMBERS if k in limits}
    ok = bool(compared) and all(c["value"] is not None
                                and c["value"] <= c["limit"]
                                for c in compared.values())
    return ok, compared
