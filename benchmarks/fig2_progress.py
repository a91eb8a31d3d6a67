"""Fig. 2: LP objective / topology quality over synthesis time, vs the
TPU-constrained random baseline."""
from __future__ import annotations

import argparse
import pickle

import numpy as np

from benchmarks.common import RESULTS, emit


def main(full: bool = False) -> None:
    from repro.core import topology as T
    from repro.core.mcf import mcf_uniform

    p = RESULTS / "tons_128.pkl"
    if p.exists():
        d = pickle.load(open(p, "rb"))
        lams, times = d["lambdas"], d["times"]
        print("# LP-relaxation objective over greedy iterations "
              "(128 nodes):")
        idx = np.linspace(0, len(lams) - 1, min(8, len(lams))).astype(int)
        for i in idx:
            print(f"  t={times[i]:7.1f}s  lambda={lams[i]:.5f}")
        print(f"  final integral mcf={d['mcf']:.5f}")
        emit("fig2_final_mcf", times[-1] * 1e6, f"{d['mcf']:.5f}")

    # random (TPU-constrained) baseline band
    vals = []
    for s in range(4 if not full else 16):
        topo = T.random_topology((4, 4, 8), seed=s)
        lam, _ = mcf_uniform(topo.edges(), topo.n,
                             perms=None, prefer="highs")
        vals.append(lam)
    vals = np.array(vals)
    print(f"  random baseline: mean={vals.mean():.5f} "
          f"std={vals.std():.5f} max={vals.max():.5f}")
    emit("fig2_random_mean", 0, f"{vals.mean():.5f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
