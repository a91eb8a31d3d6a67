"""Plain reference for a configuration's fabric and its routed tables.

It imports nothing of the program under test. It builds the directed
channels of a configuration from the configuration file alone (pod
shape, fabric kind, optical edge list) and judges a routed path table
against them: every served flow must be a walk from its source to its
destination, every reachable pair must be served, and the per-channel
loads, the hops per VC and the channel-dependency graph are recomputed
from the hops.

Channel numbering follows the fabric's documented layout: undirected
edges are the in-cube electrical mesh (sorted, u < v) followed by the
optical edges of the fabric kind (``bench/fabrics/<kind>.py``, sorted,
u < v); channel ``i`` runs ``u -> v`` along edge
``i`` and channel ``E + i`` runs back.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csg

CUBE = 4            # chips per cube edge
FACE = CUBE * CUBE  # OCS colors per axis


@dataclasses.dataclass(frozen=True)
class Fabric:
    n: int
    src: np.ndarray    # (C,) channel source node
    dst: np.ndarray    # (C,) channel destination node
    color: np.ndarray  # (C,) OCS color, -1 for electrical

    @property
    def n_ch(self) -> int:
        return len(self.src)


def coords(dims) -> np.ndarray:
    X, Y, _ = dims
    i = np.arange(int(np.prod(dims)))
    return np.stack([i % X, (i // X) % Y, i // (X * Y)], axis=1)


def ids(dims, c: np.ndarray) -> np.ndarray:
    X, Y, _ = dims
    return c[:, 0] + X * (c[:, 1] + Y * c[:, 2])


def electrical_edges(dims) -> np.ndarray:
    """(E, 2) in-cube mesh links, u < v, sorted."""
    c = coords(dims)
    i = np.arange(len(c))
    parts = []
    for axis in range(3):
        nc = c.copy()
        nc[:, axis] += 1
        ok = (nc[:, axis] < dims[axis]) \
            & (nc[:, axis] // CUBE == c[:, axis] // CUBE)
        parts.append(np.stack([i[ok], ids(dims, nc[ok])], axis=1))
    e = np.concatenate(parts)
    return e[np.lexsort((e[:, 1], e[:, 0]))]


def fabric(config: dict) -> Fabric:
    """The directed channels of a configuration file's fabric, whose
    optical links come from ``bench/fabrics/<fabric>.py``."""
    from bench.harness import load_module
    dims = tuple(config["pod"])
    opt = load_module("fabrics", config["fabric"]).optical(config)
    e = electrical_edges(dims)
    und = np.concatenate([e, opt[:, :2]])
    col = np.concatenate([np.full(len(e), -1), opt[:, 2]])
    return Fabric(int(np.prod(dims)),
                  np.concatenate([und[:, 0], und[:, 1]]).astype(np.int64),
                  np.concatenate([und[:, 1], und[:, 0]]).astype(np.int64),
                  np.concatenate([col, col]).astype(np.int64))


def color_channels(fab: Fabric, color: int) -> np.ndarray:
    """Sorted ids of every channel through OCS ``color``."""
    return np.nonzero(fab.color == color)[0]


def reachable(fab: Fabric, dead: Optional[np.ndarray] = None
              ) -> np.ndarray:
    """(n, n) bool: a directed path over live channels leads from row to
    column."""
    alive = np.ones(fab.n_ch, bool)
    if dead is not None:
        alive[dead] = False
    a = sp.csr_matrix((np.ones(int(alive.sum())),
                       (fab.src[alive], fab.dst[alive])),
                      shape=(fab.n, fab.n))
    return np.isfinite(csg.shortest_path(a, unweighted=True))


@dataclasses.dataclass(frozen=True)
class Table:
    """A routed path table as plain arrays (row-major flows)."""
    src_indptr: np.ndarray  # (n + 1,)
    dst: np.ndarray         # (F,)
    hop_indptr: np.ndarray  # (F + 1,)
    chan: np.ndarray        # (H,)
    vc: np.ndarray          # (H,)
    n_vc: int

    @classmethod
    def of(cls, t) -> "Table":
        """Copy the arrays of any object that carries them."""
        return cls(*(np.asarray(getattr(t, k), np.int64) for k in
                     ("src_indptr", "dst", "hop_indptr", "chan", "vc")),
                   int(t.n_vc))


def table_report(fab: Fabric, t: Table,
                 dead: Optional[np.ndarray] = None) -> dict:
    """Judge a routed table against the fabric.

    ``walk_errors``: served flows that are not a walk from their source
    to their destination over existing channels, with VCs in range, or
    that repeat a pair. ``dead_hops``: hops on a ``dead`` channel.
    ``missing_pairs``: pairs with a path over live channels and no
    served flow. ``cdg_cyclic``: (channel, VC) queues on a cycle of the
    channel-dependency graph (0 means deadlock-free). ``loads`` (flows
    per channel) and ``vc_counts`` (hops per VC) are recomputed from the
    served hops.
    """
    n, C = fab.n, fab.n_ch
    F = len(t.dst)
    flen = np.diff(t.hop_indptr)
    flow_src = np.repeat(np.arange(n), np.diff(t.src_indptr))
    served = flen > 0
    hop_flow = np.repeat(np.arange(F), flen)
    bad = np.zeros(F, bool)
    in_range = (t.chan >= 0) & (t.chan < C) \
        & (t.vc >= 0) & (t.vc < t.n_vc)
    bad[hop_flow[~in_range]] = True
    ch = np.clip(t.chan, 0, C - 1)
    first = t.hop_indptr[:-1][served]
    last = t.hop_indptr[1:][served] - 1
    sf = np.nonzero(served)[0]
    bad[sf[fab.src[ch[first]] != flow_src[sf]]] = True
    bad[sf[fab.dst[ch[last]] != t.dst[sf]]] = True
    same = hop_flow[1:] == hop_flow[:-1]
    broken = same & (fab.dst[ch[:-1]] != fab.src[ch[1:]])
    bad[hop_flow[1:][broken]] = True
    pair = flow_src[sf] * n + t.dst[sf]
    _, first_seen = np.unique(pair, return_index=True)
    dup = np.ones(len(sf), bool)
    dup[first_seen] = False
    bad[sf[dup]] = True

    have = np.zeros((n, n), bool)
    have[flow_src[sf], t.dst[sf]] = True
    want = reachable(fab, dead)
    np.fill_diagonal(want, False)

    node = ch * t.n_vc + np.clip(t.vc, 0, t.n_vc - 1)
    a, b = node[:-1][same], node[1:][same]
    S = C * t.n_vc
    g = sp.csr_matrix((np.ones(len(a)), (a, b)), shape=(S, S))
    _, label = csg.connected_components(g, directed=True,
                                        connection="strong")
    size = np.bincount(label, minlength=S)
    cyclic = size[label] > 1
    cyclic[a[a == b]] = True
    return {
        "walk_errors": int(bad.sum()),
        "dead_hops": 0 if dead is None else int(np.isin(t.chan, dead).sum()),
        "missing_pairs": int((want & ~have).sum()),
        "cdg_cyclic": int(cyclic.sum()),
        "loads": np.bincount(ch, minlength=C),
        "vc_counts": np.bincount(np.clip(t.vc, 0, t.n_vc - 1),
                                 minlength=t.n_vc),
    }
