"""Traffic pattern ``uniform``: every chip sends to every other chip
with equal weight, the TONS paper's first pattern. No parameters.

``program`` builds the program's pattern; ``demand`` is the plain
reference's definition of the same mix and imports nothing of the
program.
"""
import numpy as np


def program(params: dict, n: int):
    from repro.core.traffic import TrafficPattern
    return TrafficPattern.uniform(n)


def demand(params: dict, n: int):
    """(n, n) float64 demand and (n,) float32 source intensity."""
    m = np.ones((n, n))
    np.fill_diagonal(m, 0.0)
    mass = m.sum(axis=1)
    return m, (mass / mass[mass > 0].mean()).astype(np.float32)
