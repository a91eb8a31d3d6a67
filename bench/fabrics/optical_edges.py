"""Fabric kind ``optical_edges``: the in-cube meshes of the
configuration's ``pod`` shape plus the optical links listed under
``optical`` as ``[u, v, color]``.

``topology`` builds the program's ``Topology``; ``optical`` is the plain
reference's list of the same optical links and imports nothing of the
program.
"""
import numpy as np


def topology(config: dict):
    from repro.core import topology as T
    return T.Topology(T.Pod(tuple(config["pod"])),
                      [tuple(e) for e in config["optical"]],
                      name=config["name"])


def optical(config: dict) -> np.ndarray:
    return np.asarray(config["optical"], np.int64).reshape(-1, 3)
