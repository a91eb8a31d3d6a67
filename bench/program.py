"""The program's own spans and counters (``repro.core.obs``) that fall in
a run's window, for the per-layer readers.

The window is the harness's ``window`` record, on the same
``time.perf_counter`` clock as the program's spans; a span counts when
it starts inside the window. A checkout whose program keeps no such
record gives nothing, and its readers return None.
"""
from __future__ import annotations

from typing import List, Optional, Tuple


def window(run) -> Optional[Tuple[float, float]]:
    w = [(t, t + s) for name, t, s in run.spans.records if name == "window"]
    return w[0] if len(w) == 1 else None


def _recorder():
    try:
        from repro.core import obs
    except ImportError:
        return None
    return obs.RECORDER


def spans(run, name: str) -> list:
    """The program's closed spans named ``name`` in the run's window."""
    rec, w = _recorder(), window(run)
    return [] if rec is None or w is None else rec.between(*w, name)


def counts(run, name: str) -> List[int]:
    """The readings of the program's counter ``name`` in the window."""
    rec, w = _recorder(), window(run)
    return [] if rec is None or w is None else \
        [c.value for c in rec.counts_between(*w, name)]


def seconds_per(run, name: str, per: str) -> Optional[float]:
    """Seconds of the spans ``name`` over the number of spans ``per``
    (one per call) in the window; None where either is missing."""
    num, den = spans(run, name), spans(run, per)
    return sum(s.seconds for s in num) / len(den) if num and den else None
