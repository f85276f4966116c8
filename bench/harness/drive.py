"""Drive the engine: warm-up, then an open or a closed loop for the window.

The harness wraps its own calls into the engine (``bench.submit``,
``bench.step``) and its waits for arrivals (``bench.wait``) in
``jax.profiler.TraceAnnotation``, so a traced run can say what the host
was doing in each idle gap of the device.

Every token is stamped with the host clock when the ``step()`` that
produced it returns: that is when a streaming client would see it.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax

from bench.harness.traffic import Planned

clock = time.monotonic


@dataclass
class ReqRecord:
    index: int
    prompt_len: int
    max_new: int
    due: float                          # absolute clock (open loop)
    submit: float = 0.0
    handle: int = -1
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finished: bool = False


@dataclass
class DriveResult:
    t0: float                           # window opens
    t_end: float                        # window closes
    records: Dict[int, ReqRecord]
    lateness: List[float]               # submit - due, open loop only
    steps: int = 0
    # (seconds, clock at its start) of each step and wait: see Client
    stalls: List[Tuple[float, float]] = field(default_factory=list)


def _request(p: Planned):
    from repro.serving import Request
    return Request(p.index, p.prompt, max_new_tokens=p.max_new)


class Client:
    """Submits planned requests and steps the engine, recording tokens."""

    def __init__(self, engine, planned: List[Planned]):
        self.engine = engine
        self.planned = planned
        self.records: Dict[int, ReqRecord] = {}
        self.next = 0                   # next planned request to submit
        # wall seconds of each step() and each wait's overshoot past its
        # wake-up, with the clock at its start: the longest say whether a
        # slow run stalled in the engine or on the host, and when
        self.stalls: List[Tuple[float, float]] = []

    def submit(self, due: float = 0.0) -> ReqRecord:
        p = self.planned[self.next]
        self.next += 1
        rec = ReqRecord(p.index, len(p.prompt), p.max_new, due)
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec.submit = clock()
            rec.handle = self.engine.submit(_request(p))
        self.records[p.index] = rec
        return rec

    def step(self) -> List[int]:
        """One engine step; returns the request ids that finished."""
        t0 = clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            events = self.engine.step()
        t = clock()
        self.stalls.append((t - t0, t0))
        done = []
        for ev in events:
            rec = self.records[ev.request_id]
            if ev.kind == "token":
                rec.tokens.append(int(ev.token))
                rec.token_times.append(t)
            elif ev.kind == "finished":
                rec.finished = True
                done.append(ev.request_id)
        return done


def decode_buckets(sched, lo: int, hi: int) -> List[int]:
    """Every decode bucket a live position in [lo, hi] can select."""
    return sorted({sched.decode_bucket(t) for t in range(lo, hi + 1,
                                                         sched.bucket_width)}
                  | {sched.decode_bucket(hi)})


def warm_prompts(sched, max_len: int, planned: List[Planned],
                 first: List[Planned] = ()) -> Dict[str, List[int]]:
    """The prompt lengths that warm every prefill and decode program the
    window can touch.  A prompt of ``n`` tokens with two new tokens runs
    the prefill bucket of ``n`` and then one decode step at position
    ``n``.  For each decode bucket the shortest such prompt is taken
    whose prefill bucket the window runs anyway, else the shortest one
    in the bucket; prefill buckets still cold get a prompt of their own.
    ``first`` are the requests a closed loop's fill admits together:
    their prefill buckets and their first decode bucket need no
    warming."""
    prompts = [len(p.prompt) for p in planned]
    ends = [len(p.prompt) + p.max_new for p in planned]
    top = max_len - 2                   # room for the decode step's row
    pre = {sched.prefill_len(n) for n in prompts}
    dec = set(decode_buckets(sched, min(prompts), min(max(ends),
                                                      max_len - 1)))
    if first:
        done_pre = {sched.prefill_len(len(p.prompt)) for p in first}
        done_dec = {sched.decode_bucket(max(len(p.prompt) for p in first))}
    else:
        done_pre, done_dec = set(), set()
    lengths = []
    for b in sorted(dec - done_dec):
        cands = [n for n in range(1, top + 1) if sched.decode_bucket(n) == b]
        if cands:
            known = [n for n in cands if sched.prefill_len(n) in pre]
            lengths.append(min(known or cands))
    warmed = done_pre | {sched.prefill_len(n) for n in lengths}
    lengths += [min(b, top) for b in sorted(pre - warmed)]
    return {"prompts": lengths,
            "prefill": sorted({sched.prefill_len(n) for n in lengths}),
            "decode": sorted({sched.decode_bucket(n) for n in lengths})}


def warm_up(engine, planned: List[Planned],
            first: List[Planned] = ()) -> Dict[str, List[int]]:
    """Run every prefill and decode program the window can touch
    (:func:`warm_prompts`), through the engine's public entry points
    only: one request at a time, so that its decode step runs at its
    own position, each drained before the next."""
    from repro.serving import Request
    plan = warm_prompts(engine.sched, engine.max_len, planned, first)
    for i, n in enumerate(plan["prompts"]):
        engine.submit(Request(-1 - i, [1] * n, max_new_tokens=2))
        engine.drain()
    return plan


def fill(client: Client, slots: int) -> None:
    """Closed loop set-up: occupy every slot and prefill it."""
    for _ in range(min(slots, len(client.planned))):
        client.submit()
    client.step()


def run_closed(client: Client, seconds: float,
               on_tick: Optional[Callable[[float], None]] = None
               ) -> DriveResult:
    """Step until the window closes, refilling each finished slot at once
    from the seeded queue."""
    client.stalls.clear()
    t0 = clock()
    t_end = t0 + seconds
    steps = 0
    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now)
        if now >= t_end or not client.engine.has_work():
            break
        for _ in client.step():
            if client.next < len(client.planned):
                client.submit()
        steps += 1
    return DriveResult(t0, t_end, client.records, [], steps, client.stalls)


def run_open(client: Client, seconds: float, wait_after: float = 60.0,
             on_tick: Optional[Callable[[float], None]] = None
             ) -> DriveResult:
    """Submit each request at its due time whatever the engine is doing;
    after the window closes, keep the arrivals coming until every
    request due in the window has its first token (at most
    ``wait_after`` seconds)."""
    planned = client.planned
    client.stalls.clear()
    t0 = clock()
    t_end = t0 + seconds
    lateness: List[float] = []
    steps = 0
    in_window = sum(1 for p in planned if p.due <= seconds)
    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now)
        while client.next < len(planned) \
                and t0 + planned[client.next].due <= now:
            rec = client.submit(t0 + planned[client.next].due)
            lateness.append(rec.submit - rec.due)
        if now >= t_end:
            waiting = [r for r in client.records.values()
                       if r.due <= t_end and not r.tokens]
            if (client.next >= in_window and not waiting) \
                    or now >= t_end + wait_after:
                break
        if client.engine.has_work():
            client.step()
            steps += 1
        elif client.next < len(planned):
            nxt = t0 + planned[client.next].due
            wake = min(nxt, t_end) if now < t_end else nxt
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, wake - clock()))
            client.stalls.append((max(0.0, clock() - wake), wake))
        else:
            break
    return DriveResult(t0, t_end, client.records, lateness, steps,
                       client.stalls)
