"""(min, +) matrix multiply as a Pallas TPU kernel.

The paper-side compute hotspot: all-pairs shortest paths / metric closure
(diameter, average hops, candidate path sets at pod scale) is repeated
(min,+) squaring of the hop matrix. On TPU this is a matmul-shaped
streaming problem: 128x128 VMEM tiles, K innermost so the accumulator
carries in VMEM; the semiring runs on the VPU (no MXU for min/+, but the
tiling/bandwidth structure is identical to a matmul).

Validated under interpret=True against ref.minplus_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e9


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, bk: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, BIG)

    a = a_ref[...]                       # (bm, bk)
    b = b_ref[...]                       # (bk, bn)
    # (min,+) contraction over the bk tile
    s = jnp.min(a[:, :, None] + b[None, :, :], axis=1)
    acc_ref[...] = jnp.minimum(acc_ref[...], s)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def minplus(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
            interpret: bool = False):
    """out[i, j] = min_k a[i, k] + b[k, j]; a: (M, K), b: (K, N) f32."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (M // bm, N // bn, K // bk)
    kernel = functools.partial(_kernel, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)


def apsp(adj, *, interpret: bool = False, block: int = 128):
    """All-pairs hop distances by log-depth (min,+) squaring."""
    import math
    n = adj.shape[0]
    d = adj
    for _ in range(int(math.ceil(math.log2(max(n - 1, 1))))):
        d = minplus(d, d, bm=block, bn=block, bk=block,
                    interpret=interpret)
    return d
