"""Fig. 11 (workload co-design): specialized vs generic TONS vs torus.

Renders the headline comparison of ``bench_workload``: for each
registered workload, the demand-weighted MCF and the trace-replay
saturation of the workload-specialized fabric, the generic
uniform-demand TONS, and the PT torus, normalized to the torus.

Cheap by construction: reads BENCH_workload.json when present
(written by ``bench_workload --json``, which ``run.py`` executes
earlier in the same suite pass); otherwise falls back to an
analytic-only comparison -- weighted MCF of the cached topologies
(``tons_wl_<n>_<arch>.pkl`` / ``tons_<n>.pkl``) without any synthesis
or simulation, skipping fabrics whose caches are absent.
"""
from __future__ import annotations

import argparse
import json
import pickle
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks.common import RESULTS, emit, load_tons


def _bars(label: str, vals: dict, base: float) -> None:
    for name, v in vals.items():
        norm = v / max(base, 1e-12)
        bar = "#" * max(1, int(round(norm * 20)))
        print(f"  {label:28s} {name:11s} {v:.5f} ({norm:.2f}x) {bar}")


def _from_bench(d: dict) -> None:
    for sname, size in d.get("sizes", {}).items():
        for arch, row in size.get("workloads", {}).items():
            for metric, key in (("weighted MCF", "weighted_mcf"),
                                ("trace saturation",
                                 "trace_saturation")):
                vals = {"specialized": row["specialized"][key],
                        "pt": row["pt"][key]}
                if "generic" in row:
                    vals["generic"] = row["generic"][key]
                _bars(f"{sname} {arch} {metric}", vals, row["pt"][key])
        if "tenants" in size:
            pt = size["tenants"]["per_tenant"]
            print(f"  {sname} shared fabric "
                  f"({size['tenants']['fabric']}): " + " ".join(
                      f"{k} delivered={v['delivered']:.4f}"
                      for k, v in pt.items()))
    r = d["sizes"]["n128"]["workloads"]
    for arch, row in r.items():
        emit(f"fig11_{arch.split('-')[0]}_mcf_vs_pt", 0,
             f"{row['mcf_vs_pt']:.3f}x")


def _analytic_fallback() -> None:
    """No bench record yet: weighted MCF only, cached topologies only."""
    import numpy as np

    from repro.core import demand as D, topology as T, workload as W

    spec, n = (4, 4, 8), 128
    generic = load_tons(n)
    pt = T.pt(spec)
    for arch, shape in (("deepseek-moe-16b", "train_4k"),
                        ("gemma-7b", "train_4k")):
        wd = W.workload_demand(spec, arch, shape)
        vals = {"pt": D.weighted_mcf(pt, wd)}
        if generic:
            vals["generic"] = D.weighted_mcf(generic[0], wd)
        pkl = RESULTS / f"tons_wl_{n}_{arch}.pkl"
        if pkl.exists():
            cached = pickle.load(open(pkl, "rb"))
            topo = T.Topology(T.Pod(spec),
                              [tuple(e) for e in cached["optical"]],
                              name=f"TONS-wl {arch}")
            vals["specialized"] = D.weighted_mcf(topo, wd)
        else:
            print(f"  n128 {arch}: no specialized cache "
                  f"(run bench_workload first)")
        _bars(f"n128 {arch} weighted MCF", vals, vals["pt"])


def main(full: bool = False) -> None:
    bench = Path(__file__).parent.parent / "BENCH_workload.json"
    print("# workload co-design (fig 11): specialized vs generic vs "
          "torus, normalized to PT")
    if bench.exists():
        _from_bench(json.loads(bench.read_text()))
    else:
        _analytic_fallback()


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
