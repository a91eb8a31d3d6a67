"""Fabric kind ``torus``: the prismatic torus of the configuration's
``pod`` shape, 4^3 cubes whose faces are joined by OCS into a torus.

``topology`` builds the program's ``Topology``; ``optical`` is the plain
reference's list of the same optical links and imports nothing of the
program.
"""
import numpy as np

from bench.reference.fabric import CUBE, FACE, coords, ids


def topology(config: dict):
    from repro.core import topology as T
    return T.pt(tuple(config["pod"]))


def optical(config: dict) -> np.ndarray:
    """(E, 3) optical links (u, v, color), u < v: every chip on a cube's
    high face along an axis links to its torus neighbour along that
    axis; the color is the OCS of that face position (axis * 16 +
    position of the other two in-cube coords)."""
    dims = tuple(config["pod"])
    c = coords(dims)
    i = np.arange(len(c))
    inc = c % CUBE
    parts = []
    for axis in range(3):
        on = inc[:, axis] == CUBE - 1
        a, b = [k for k in range(3) if k != axis]
        color = axis * FACE + inc[on, a] * CUBE + inc[on, b]
        nc = c[on].copy()
        nc[:, axis] = (nc[:, axis] + 1) % dims[axis]
        u, v = i[on], ids(dims, nc)
        parts.append(np.stack([np.minimum(u, v), np.maximum(u, v), color],
                              axis=1))
    return np.unique(np.concatenate(parts), axis=0)
