"""Traffic pattern ``hotspot``: a share ``frac`` of every chip's traffic
goes to the chips in ``hot``, the rest uniformly to the others.

``program`` builds the program's pattern; ``demand`` is the plain
reference's definition of the same mix and imports nothing of the
program.
"""
import numpy as np


def program(params: dict, n: int):
    from repro.core.traffic import TrafficPattern
    return TrafficPattern.hotspot(n, hot=params["hot"], frac=params["frac"])


def demand(params: dict, n: int):
    """(n, n) float64 demand and (n,) float32 source intensity."""
    frac = float(params["frac"])
    hot = np.asarray(sorted(set(params["hot"])), np.int64)
    cold = np.ones((n, n))
    cold[:, hot] = 0.0
    np.fill_diagonal(cold, 0.0)
    m = cold / np.maximum(cold.sum(axis=1, keepdims=True), 1e-12) \
        * (1.0 - frac)
    h = np.zeros((n, n))
    h[:, hot] = 1.0
    np.fill_diagonal(h, 0.0)
    m = m + h / np.maximum(h.sum(axis=1, keepdims=True), 1e-12) * frac
    np.fill_diagonal(m, 0.0)
    return m, np.ones(n, np.float32)
