"""First-order LP solver in JAX (PDHG / PDLP-lite) + HiGHS oracle.

Problem form:   min  c.x   s.t.  A x <= b,  lo <= x <= hi.

The paper solves its synthesis LPs with Gurobi's barrier method (sparse
factorizations, 256 GB machines, days at pod scale). Our TPU-native
adaptation is matrix-free PDHG over a COO operator: every iteration is two
segment-sums and two clips -- bandwidth-bound streaming ops that map onto
accelerators, with Ruiz equilibration, power-iteration step sizing and
averaging restarts for convergence quality. scipy's HiGHS is kept as an
exactness oracle for small instances (tests / Fig.1-scale runs).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class COOMatrix:
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    shape: Tuple[int, int]

    @staticmethod
    def from_triplets(rows, cols, vals, shape) -> "COOMatrix":
        return COOMatrix(np.asarray(rows, np.int32),
                         np.asarray(cols, np.int32),
                         np.asarray(vals, np.float64), shape)

    def to_scipy(self):
        import scipy.sparse as sp
        return sp.coo_matrix((self.vals, (self.rows, self.cols)),
                             shape=self.shape).tocsr()


@dataclasses.dataclass
class LPResult:
    x: np.ndarray
    y: Optional[np.ndarray]
    obj: float
    status: str
    iters: int = 0
    rel_gap: float = 0.0
    primal_infeas: float = 0.0


def solve_highs(c, A: COOMatrix, b, lo, hi,
                method: str = "highs", **options) -> LPResult:
    """HiGHS oracle. Extra ``options`` are forwarded to scipy's linprog
    (e.g. ``ipm_optimality_tolerance=1e-4`` -- the synthesis fixing loop
    only consumes the *ordering* of the fractional m values, so loose
    IPM tolerances buy large-instance wall-clock at no quality cost)."""
    from scipy.optimize import linprog
    res = linprog(c, A_ub=A.to_scipy(), b_ub=b,
                  bounds=np.stack([lo, hi], axis=1), method=method,
                  options=options or None)
    y = None
    if res.status == 0 and hasattr(res, "ineqlin"):
        y = -np.asarray(res.ineqlin.marginals)
    return LPResult(res.x if res.x is not None else np.zeros_like(c),
                    y, float(res.fun) if res.fun is not None else np.nan,
                    "optimal" if res.status == 0 else f"status{res.status}")


def _ruiz_scale(A: COOMatrix, iters: int = 10):
    m, n = A.shape
    dr = np.ones(m)
    dc = np.ones(n)
    vals = A.vals.copy()
    for _ in range(iters):
        rmax = np.zeros(m)
        np.maximum.at(rmax, A.rows, np.abs(vals))
        rmax[rmax == 0] = 1.0
        vals /= np.sqrt(rmax)[A.rows]
        dr /= np.sqrt(rmax)
        cmax = np.zeros(n)
        np.maximum.at(cmax, A.cols, np.abs(vals))
        cmax[cmax == 0] = 1.0
        vals /= np.sqrt(cmax)[A.cols]
        dc /= np.sqrt(cmax)
    return vals, dr, dc


@partial(jax.jit, static_argnames=("m", "n", "inner"))
def _pdhg_chunk(rows, cols, vals, c, b, lo, hi, x, y, tau, sigma, m, n,
                inner):
    def matvec(v):
        return jax.ops.segment_sum(vals * v[cols], rows, num_segments=m)

    def rmatvec(u):
        return jax.ops.segment_sum(vals * u[rows], cols, num_segments=n)

    def body(i, carry):
        x, y, xs, ys = carry
        g = c + rmatvec(y)
        x_new = jnp.clip(x - tau * g, lo, hi)
        r = matvec(2.0 * x_new - x) - b
        y_new = jnp.maximum(0.0, y + sigma * r)
        return x_new, y_new, xs + x_new, ys + y_new

    x, y, xs, ys = jax.lax.fori_loop(
        0, inner, body, (x, y, jnp.zeros_like(x), jnp.zeros_like(y)))
    return x, y, xs / inner, ys / inner


def _residuals(A_sp, c, b, lo, hi, x, y):
    ax = A_sp @ x
    pinf = np.linalg.norm(np.maximum(ax - b, 0.0)) / (1 + np.linalg.norm(b))
    pobj = float(c @ x)
    r = c + (A_sp.T @ y)
    dobj = float(-b @ y + np.sum(np.where(r > 0, lo * r, hi * r)))
    gap = abs(pobj - dobj) / (1 + abs(pobj) + abs(dobj))
    return pobj, dobj, gap, pinf


def solve_pdhg(c, A: COOMatrix, b, lo, hi, max_iters: int = 40000,
               tol: float = 1e-5, inner: int = 250,
               x0: Optional[np.ndarray] = None,
               y0: Optional[np.ndarray] = None,
               verbose: bool = False) -> LPResult:
    """Matrix-free PDHG with restarts; the iterates stay in f64.

    64-bit mode is switched on for this call only, so the f64 arrays and
    the ``_pdhg_chunk`` executions live inside it and nothing else in the
    process changes dtype."""
    with jax.enable_x64(True):
        m, n = A.shape
        c = np.asarray(c, np.float64)
        b = np.asarray(b, np.float64)
        lo = np.asarray(lo, np.float64)
        hi = np.asarray(hi, np.float64)

        vals_s, dr, dc = _ruiz_scale(A)
        # scaled problem: x = Dc xs, rows scaled by Dr:
        cs = c * dc
        bs = b * dr
        los = lo / dc
        his = hi / dc

        A_sp = A.to_scipy()

        # spectral norm of the scaled operator (power iteration)
        import scipy.sparse as sp
        As = sp.coo_matrix((vals_s, (A.rows, A.cols)), shape=A.shape).tocsr()
        v = np.random.default_rng(0).normal(size=n)
        v /= np.linalg.norm(v)
        for _ in range(60):
            w = As.T @ (As @ v)
            nw = np.linalg.norm(w)
            if nw == 0:
                break
            v = w / nw
        norm = float(np.sqrt(max(v @ (As.T @ (As @ v)), 1e-12)))
        step = 0.9 / max(norm, 1e-9)
        tau = sigma = step

        rows_j = jnp.asarray(A.rows)
        cols_j = jnp.asarray(A.cols)
        vals_j = jnp.asarray(vals_s, jnp.float64)
        cj = jnp.asarray(cs)
        bj = jnp.asarray(bs)
        loj = jnp.asarray(los)
        hij = jnp.asarray(his)

        x = np.clip(x0 / dc, los, his) if x0 is not None \
            else np.clip(np.zeros(n), los, his)
        y = (y0 / dr) if y0 is not None else np.zeros(m)
        xj = jnp.asarray(x)
        yj = jnp.asarray(np.maximum(y, 0.0))

        best = None
        it = 0
        while it < max_iters:
            xj, yj, xavg, yavg = _pdhg_chunk(rows_j, cols_j, vals_j, cj, bj,
                                             loj, hij, xj, yj, tau, sigma,
                                             m, n, inner)
            it += inner
            # evaluate averaged and current iterates in the original space
            x_avg_u = np.asarray(xavg) * dc
            y_avg_u = np.asarray(yavg) * dr
            x_cur_u = np.asarray(xj) * dc
            y_cur_u = np.asarray(yj) * dr
            for xu, yu, tag in ((x_avg_u, y_avg_u, "avg"),
                                (x_cur_u, y_cur_u, "cur")):
                pobj, dobj, gap, pinf = _residuals(A_sp, c, b, lo, hi, xu, yu)
                if best is None or (gap + pinf) < (best[2] + best[3]):
                    best = (xu, yu, gap, pinf, pobj, tag)
            if verbose:
                print(f"  pdhg it={it} gap={best[2]:.2e} pinf={best[3]:.2e} "
                      f"obj={best[4]:.6g} ({best[5]})")
            if best[2] < tol and best[3] < tol:
                break
            # restart from the best candidate (rescaled)
            xj = jnp.asarray(best[0] / dc)
            yj = jnp.asarray(best[1] / dr)

        xu, yu, gap, pinf, pobj, _ = best
        status = "optimal" if (gap < tol and pinf < tol) else "max_iters"
        return LPResult(xu, yu, pobj, status, iters=it, rel_gap=gap,
                        primal_infeas=pinf)


def solve(c, A: COOMatrix, b, lo, hi, prefer: str = "auto",
          **kw) -> LPResult:
    """auto: HiGHS for small instances, PDHG otherwise."""
    small = A.shape[0] * A.shape[1] < 5e9 and len(A.vals) < 3e6 \
        and A.shape[1] < 200000
    if prefer == "highs" or (prefer == "auto" and small):
        try:
            res = solve_highs(c, A, b, lo, hi)
            if res.status == "optimal":
                return res
        except Exception:
            pass
    return solve_pdhg(c, A, b, lo, hi, **kw)
