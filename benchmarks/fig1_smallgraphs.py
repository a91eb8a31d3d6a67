"""Fig. 1: analytical MCF of directed 4-radix topologies vs TONS synthesis."""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import emit, timed


def main(full: bool = False) -> None:
    from repro.core import smallgraphs as SG
    sizes = [10, 15, 20] if not full else [10, 15, 20, 25, 30, 40]
    r = 4
    kautz_sizes = SG.kautz_sizes(r, max(sizes))
    print("# n, kautz, genkautz, xpander, jellyfish(best of 20), tons")
    for n in sizes:
        row = {"kautz": None}
        if n in kautz_sizes:
            row["kautz"] = n * SG.directed_mcf(SG.kautz(r, kautz_sizes[n]),
                                               n)
        row["genkautz"] = n * SG.directed_mcf(SG.gen_kautz(n, r), n)
        xp = SG.xpander(n, r)
        row["xpander"] = n * SG.directed_mcf(xp, n) if xp is not None \
            else None
        best_jf = 0.0
        for s in range(20):
            jf = SG.jellyfish(n, r, seed=s)
            if jf is not None:
                best_jf = max(best_jf, SG.directed_mcf(jf, n))
        row["jellyfish"] = n * best_jf
        (edges, _), us = timed(SG.synthesize_directed, n, r,
                               interval=1 if n <= 20 else max(2, n // 10),
                               restarts=3 if n <= 25 else 2)
        row["tons"] = n * SG.directed_mcf(edges, n)
        fmt = {k: (f"{v:.4f}" if v else "-") for k, v in row.items()}
        print(f"  n={n:3d} " + " ".join(f"{k}={v}" for k, v in fmt.items()))
        best_base = max(v for k, v in row.items()
                        if k != "tons" and v is not None)
        emit(f"fig1_n{n}", us, f"tons/best_baseline="
             f"{row['tons'] / best_base:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
