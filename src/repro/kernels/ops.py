"""jit'd public wrappers for the Pallas kernels.

The wrappers compile the kernels for the TPU. ``interpret=True`` runs
them in the Pallas interpreter instead, which is how the CPU tests call
them. The jnp reference implementations remain available as oracles and
as the XLA fallback the models use for the dry-run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.minplus import apsp as _apsp, minplus as _minplus

@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool = False):
    return _flash(q, k, v, causal=causal, bq=bq, bk=bk,
                  interpret=interpret)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus(a, b, bm: int = 128, bn: int = 128, bk: int = 128,
            interpret: bool = False):
    return _minplus(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


def hop_matrix(edges: np.ndarray, n: int) -> jnp.ndarray:
    """Adjacency -> initial (min,+) distance matrix."""
    d = np.full((n, n), 1e9, np.float32)
    np.fill_diagonal(d, 0.0)
    d[edges[:, 0], edges[:, 1]] = 1.0
    d[edges[:, 1], edges[:, 0]] = 1.0
    return jnp.asarray(d)


def topology_metrics(edges: np.ndarray, n: int, block: int = 128,
                     interpret: bool = False):
    """Diameter + average hops via the Pallas APSP path (padded to the
    block size)."""
    pad = (-n) % block
    d0 = hop_matrix(edges, n)
    if pad:
        d0 = jnp.pad(d0, ((0, pad), (0, pad)), constant_values=1e9)
        d0 = d0.at[jnp.arange(n, n + pad), jnp.arange(n, n + pad)].set(0.0)
    d = _apsp(d0, interpret=interpret, block=block)
    d = d[:n, :n]
    diam = int(jnp.max(jnp.where(d >= 1e8, -1, d)))
    avg = float(jnp.sum(jnp.where(d >= 1e8, 0, d)) / (n * (n - 1)))
    return diam, avg
