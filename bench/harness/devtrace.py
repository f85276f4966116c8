"""The profiler's trace -> device events, busy time and idle gaps.

A traced run writes an ``.xplane.pb`` file; :func:`load_xplane` reads it
with ``jax.profiler.ProfileData`` into a :class:`Trace`: the device
planes' per-operation events ("XLA Ops") and per-program events ("XLA
Modules"), and the host's annotations of the harness (``bench.*``).
:meth:`Trace.to_json` / :meth:`Trace.from_json` keep that reduced form,
which is what the tests replay.

Times are seconds on the trace's own clock, shared by host and device
planes.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"


@dataclass
class Event:
    device: int
    name: str
    start: float
    dur: float
    module: str = ""               # program (jitted module) the op ran in

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def short(self) -> str:
        """The HLO instruction's name without its ``%`` and numeric
        suffix: ``%flash_decode_s1.4 = (...)`` -> ``flash_decode_s1``."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        return re.sub(r"\.\d+$", "", head)


@dataclass
class Trace:
    devices: List[int]
    ops: List[Event]
    modules: List[Event]
    host: List[Event] = field(default_factory=list)

    # --- window ------------------------------------------------------------

    def window(self) -> Tuple[float, float]:
        """The harness's ``bench.window`` span, else the device events'
        extent."""
        spans = [e for e in self.host if e.name == WINDOW]
        if spans:
            return spans[0].start, spans[0].end
        evs = self.ops or self.modules
        return min(e.start for e in evs), max(e.end for e in evs)

    def in_window(self, events: List[Event]) -> List[Event]:
        a, b = self.window()
        return [e for e in events if e.start >= a and e.end <= b]

    # --- programs ----------------------------------------------------------

    def program_kinds(self) -> Dict[str, str]:
        """Module name -> ``prefill`` / ``decode``, from the Pallas kernel
        its ops run (``flash_prefill`` / ``flash_decode_s*``): the jitted
        steps reach the trace as ``jit__unknown(<hash>)``."""
        kinds: Dict[str, str] = {}
        for op in self.ops:
            kind = op_kind(op.short)
            if kind and op.module:
                kinds.setdefault(op.module, kind)
        return kinds

    def self_times(self) -> List[Tuple[Event, float]]:
        """Each op in the window with its self time: its duration less
        the ops nested in it (a ``while`` holds its loop body's ops)."""
        out = []
        by_dev: Dict[int, List[Event]] = {}
        for e in self.in_window(self.ops):
            by_dev.setdefault(e.device, []).append(e)
        for evs in by_dev.values():
            evs.sort(key=lambda e: (e.start, -e.dur))
            stack: List[int] = []
            selfs = [e.dur for e in evs]
            for i, e in enumerate(evs):
                while stack and evs[stack[-1]].end <= e.start + 1e-12:
                    stack.pop()
                if stack and e.end <= evs[stack[-1]].end + 1e-12:
                    selfs[stack[-1]] -= e.dur
                stack.append(i)
            out += list(zip(evs, selfs))
        return out

    # --- busy / idle -------------------------------------------------------

    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        """Union of the intervals in which an operation ran on ``device``,
        clipped to the window."""
        a, b = self.window()
        evs = [e for e in (self.ops or self.modules) if e.device == device]
        ivs = sorted((max(e.start, a), min(e.end, b)) for e in evs
                     if e.end > a and e.start < b)
        out: List[Tuple[float, float]] = []
        for s, t in ivs:
            if out and s <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], t))
            else:
                out.append((s, t))
        return out

    def busy_s(self, device: int) -> float:
        return sum(t - s for s, t in self.busy_intervals(device))

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the devices; 0 where the trace
        holds no device (a host-only backend)."""
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def window_s(self) -> float:
        a, b = self.window()
        return b - a

    def idle_gaps(self, device: int) -> List[Tuple[str, float]]:
        """Each idle gap of ``device`` in the window, labelled with the
        harness annotation that covers most of it."""
        a, b = self.window()
        edges = [a]
        for s, t in self.busy_intervals(device):
            edges += [s, t]
        edges.append(b)
        spans = [e for e in self.host
                 if e.name.startswith("bench.") and e.name != WINDOW]
        gaps = []
        for s, t in zip(edges[::2], edges[1::2]):
            if t <= s:
                continue
            cover: Dict[str, float] = {}
            for h in spans:
                o = min(t, h.end) - max(s, h.start)
                if o > 0:
                    cover[h.name] = cover.get(h.name, 0.0) + o
            label = max(cover, key=cover.get) if cover else "host.other"
            gaps.append((label, t - s))
        return gaps

    # --- serialisation -----------------------------------------------------

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"devices": self.devices,
                       "ops": [asdict(e) for e in self.ops],
                       "modules": [asdict(e) for e in self.modules],
                       "host": [asdict(e) for e in self.host]}, f)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(d["devices"], [Event(**e) for e in d["ops"]],
                   [Event(**e) for e in d["modules"]],
                   [Event(**e) for e in d["host"]])


_DEVICE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")


def load_xplane(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, ops, modules, host = [], [], [], []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = int(m.group(1))
            devices.append(dev)
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                is_op = line.name == OPS_LINE
                for ev in line.events:
                    e = Event(dev, ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9)
                    if is_op:
                        e.name = e.short
                        ops.append(e)
                    else:
                        modules.append(e)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append(Event(-1, ev.name, ev.start_ns * 1e-9,
                                          ev.duration_ns * 1e-9))
    _attach_modules(ops, modules)
    return Trace(sorted(devices), ops, modules, host)


def summarize(trace_dir: str, per_line: int = 8) -> str:
    """A by-hand view of a raw trace: every plane and line, its event
    count, its most frequent event names and one event's stats."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names: Dict[str, int] = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:per_line]
            out.append(f"  LINE {line.name!r}: {len(evs)} events, top {top}")
            if evs:
                out.append(f"    stats of {evs[-1].name!r}: "
                           f"{dict(evs[-1].stats)}")
    return "\n".join(out)


def _attach_modules(ops: List[Event], modules: List[Event]) -> None:
    """Give each op without an ``hlo_module`` stat the program whose
    execution span contains it."""
    by_dev: Dict[int, List[Event]] = {}
    for m in modules:
        by_dev.setdefault(m.device, []).append(m)
    starts = {}
    for d, evs in by_dev.items():
        evs.sort(key=lambda e: e.start)
        starts[d] = [e.start for e in evs]
    for op in ops:
        if op.module or op.device not in by_dev:
            continue
        i = bisect.bisect_right(starts[op.device], op.start) - 1
        if i >= 0 and op.end <= by_dev[op.device][i].end + 1e-9:
            op.module = by_dev[op.device][i].name


def op_kind(short: str) -> Optional[str]:
    """The engine program a Pallas kernel marks: ``prefill`` / ``decode``."""
    if short.startswith("flash_prefill"):
        return "prefill"
    if short.startswith(("flash_decode", "flash_combine")):
        return "decode"
    return None
