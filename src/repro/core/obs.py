"""Spans and counters of the program's own work.

A span times one stage of a call where the work happens: it records
``(name, start, end, id, parent, root)`` on ``time.perf_counter()`` into
an in-memory ring, and opens a ``jax.profiler.TraceAnnotation`` of the
same name, so that under a profiler the span also sits on the trace's
timeline beside the device operations it launched. A counter records a
count at the same boundaries, tied to the span open around it.

Names are ``<module>.<stage>``; a child stage extends its parent's name
(``routing.select`` -> ``routing.select.bfs``). Every span of one call
shares the id of its outermost span (``root``), which serves as the
call's request id. Spans go at stage boundaries and, at most, once per
pass of a loop over shards or repair rounds; never inside a loop over
cycles, flows or packets.

The record is always on and bounded: :data:`RECORDER` keeps the newest
:data:`RING` spans and counters. One recorder serves the whole process
so that nested calls across modules (``route_pod`` -> ``select_paths``)
share parents without a recorder threaded through every signature; each
thread keeps its own stack of open spans.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Iterator, List, NamedTuple, Optional

RING = 65536   # spans (and counters) kept


class Span:
    """One timed stage; ``end`` is set when the stage closes."""
    __slots__ = ("name", "start", "end", "id", "parent", "root")

    def __init__(self, name: str, start: float, id: int, parent: int,
                 root: int):
        self.name, self.start, self.end = name, start, start
        self.id, self.parent, self.root = id, parent, root

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __repr__(self):
        return (f"Span({self.name!r}, {self.start}, {self.end}, "
                f"id={self.id}, parent={self.parent}, root={self.root})")


class Count(NamedTuple):
    """One counter reading, taken at ``time`` inside span ``span``."""
    name: str
    value: int
    time: float
    span: int
    root: int


class Recorder:
    """A bounded ring of closed spans and counter readings."""

    def __init__(self, size: int = RING):
        self.spans: collections.deque = collections.deque(maxlen=size)
        self.counts: collections.deque = collections.deque(maxlen=size)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[Span]:
        """Time the block as span ``name``; yields the :class:`Span`,
        whose ``seconds`` hold the block's duration once it closes."""
        from jax.profiler import TraceAnnotation
        stack = self._stack()
        sid = next(self._ids)
        up = stack[-1] if stack else None
        s = Span(name, 0.0, sid, up.id if up else 0, up.root if up else sid)
        stack.append(s)
        try:
            with TraceAnnotation(name):
                s.start = time.perf_counter()
                try:
                    yield s
                finally:
                    s.end = time.perf_counter()
        finally:
            stack.pop()
            self.spans.append(s)

    def count(self, name: str, value: int) -> None:
        """Record ``value`` of counter ``name`` inside the open span."""
        stack = self._stack()
        up = stack[-1] if stack else None
        self.counts.append(Count(name, int(value), time.perf_counter(),
                                 up.id if up else 0, up.root if up else 0))

    def between(self, lo: float, hi: float,
                name: Optional[str] = None) -> List[Span]:
        """Closed spans (named ``name``, if given) that start in
        ``[lo, hi]`` on the ``perf_counter`` clock."""
        return [s for s in list(self.spans) if lo <= s.start <= hi
                and (name is None or s.name == name)]

    def counts_between(self, lo: float, hi: float,
                       name: Optional[str] = None) -> List[Count]:
        """Counter readings (of ``name``, if given) taken in ``[lo, hi]``."""
        return [c for c in list(self.counts) if lo <= c.time <= hi
                and (name is None or c.name == name)]


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
