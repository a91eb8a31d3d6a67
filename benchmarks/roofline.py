"""Roofline table from the dry-run artifacts (one row per cell) +
TONS-adjusted collective terms for the MoE (all-to-all-bound) cells."""
from __future__ import annotations

import argparse
import glob
import json
from pathlib import Path

from benchmarks.common import RESULTS, emit, load_tons

DRYRUN = RESULTS / "dryrun"


def rows(mesh="single_pod_16x16"):
    out = []
    for f in sorted(glob.glob(str(DRYRUN / f"*__{mesh}.json"))):
        d = json.load(open(f))
        if "error" not in d:
            out.append(d)
    return out


def tons_collective_speedup() -> float:
    """Paper-derived fabric gain for a2a-dominant traffic: the ratio of
    TONS vs best-torus MCF at the matching pod size (128 here; the paper
    reports 1.6-2.1x at larger scales)."""
    loaded = load_tons(128)
    if not loaded:
        return 1.65
    return loaded[1]["mcf"] / 0.01364  # vs best PDTT


def main(full: bool = False) -> None:
    rs = rows()
    if not rs:
        print("no dry-run artifacts; run repro.launch.dryrun first")
        return
    print("# arch, shape, dominant, t_compute, t_memory, t_collective, "
          "useful_flop_ratio, fits_v5p")
    worst = None
    most_coll = None
    for d in rs:
        t = d["terms"]
        frac = d.get("useful_flop_ratio", 0)
        key = f"{d['arch']}|{d['shape']}"
        print(f"  {d['arch']:22s} {d['shape']:12s} {t['dominant']:13s} "
              f"{t['t_compute']:9.4f} {t['t_memory']:9.4f} "
              f"{t['t_collective']:9.4f} useful={frac:5.2f} "
              f"fits95={d.get('memory', {}).get('fits_v5p_95g')}")
        rf = t["t_compute"] / max(t["t_compute"], t["t_memory"],
                                  t["t_collective"], 1e-12)
        if d["kind"] != "decode":  # decode is trivially memory-bound
            if worst is None or rf < worst[1]:
                worst = (key, rf)
            cr = t["t_collective"] / max(t["t_compute"], 1e-12)
            if most_coll is None or cr > most_coll[1]:
                most_coll = (key, cr)
    print(f"  worst roofline fraction: {worst[0]} ({worst[1]:.4f})")
    print(f"  most collective-bound:   {most_coll[0]} "
          f"(t_coll/t_comp={most_coll[1]:.2f})")
    su = tons_collective_speedup()
    print(f"  TONS fabric a2a speedup applied to collective terms: "
          f"{su:.2f}x (paper technique -> framework integration)")
    for d in rs:
        if "moe" in d["arch"] or d["arch"].startswith("jamba"):
            t = d["terms"]
            base = t["t_collective"]
            print(f"    {d['arch']:22s} {d['shape']:12s} "
                  f"t_coll {base:.3f}s -> {base / su:.3f}s on TONS fabric")
    emit("roofline_cells", 0, f"{len(rs)}")
    emit("roofline_worst", 0, f"{worst[0]}:{worst[1]:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
