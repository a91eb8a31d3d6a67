"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, time per device program, the
device operations that took most time, and the longest idle gaps, each
named by the benchmark span that was open on the host during it.

The window is the host span ``bench.window``. A device is every plane
named ``/device:...`` that has XLA lines; its busy time is the union
of the intervals of its ``XLA Ops`` events inside the window (of its
``XLA Modules`` events where it records no ops), and the busy time
reported is the mean over the devices that ran anything. Device and
host timestamps are not aligned: in a recorded v5e trace the device's
events sit about 1.2 ms before the host span that launched them, which
moves a window's busy time by at most that much at each edge. Program
time sums the ``XLA Modules`` events by name. The ranking of operations
leaves out loops and calls, whose events enclose those of the
operations inside them.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
# operations whose events enclose the events of the operations they run
CONTAINERS = ("while", "conditional", "call")
Interval = Tuple[float, float]


def load(path) -> dict:
    """The events the reduction needs, in seconds on the trace's clock:
    ``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [...]}``, each event ``(name, start, end)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[str, dict] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"} \
                    .get(line.name)
                if key is None:
                    continue
                d = devices.setdefault(plane.name,
                                       {"ops": [], "modules": []})
                d[key] += [(short(e.name), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def short(name: str) -> str:
    """An operation's HLO name without its text: ``%fusion.8 = f32[..]
    fusion(..)`` becomes ``fusion.8``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def open_span(spans, t: float) -> str:
    """The innermost (shortest) benchmark span open at time ``t``."""
    inner = [(e - s, n) for n, s, e in spans if s <= t < e]
    return min(inner)[1] if inner else "none"


def reduce(tr: dict, top: int = 10) -> dict:
    windows = [(s, e) for n, s, e in tr["spans"] if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one bench.window span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    spans = [x for x in _clip(tr["spans"], lo, hi) if x[0] != "window"]
    busy, programs, ops = [], collections.Counter(), collections.Counter()
    gaps: List[Tuple[str, float]] = []
    for plane in sorted(tr["devices"]):
        d = tr["devices"][plane]
        mods = _clip(d["modules"], lo, hi)
        evs = _clip(d["ops"], lo, hi) or mods
        if not evs:
            continue
        for n, s, e in mods:
            programs[n] += e - s
        for n, s, e in _clip(d["ops"], lo, hi):
            if not n.startswith(CONTAINERS):
                ops[n] += e - s
        merged = union([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += [(open_span(spans, (a + b) / 2), b - a)
                 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "programs": dict(programs),
        "device_ops": [[n, s] for n, s in ops.most_common(top)],
        "idle_gaps": [list(g) for g in
                      sorted(gaps, key=lambda g: -g[1])[:top]],
    }
