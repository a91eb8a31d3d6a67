"""Figs. 9-11: AT turn prioritization, VC load balance, DOR VC skew."""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import emit, load_tons, timed


def main(full: bool = False) -> None:
    from repro.core import netsim as NS, routing as R, topology as T
    from repro.core.pipeline import PipelineConfig, route_pod
    from repro.core.vcalloc import allocate_vcs

    # --full ablates on a 512-chip 8^3 pod (synthesized TONS if cached,
    # else PDTT) -- feasible since the array routing engine; quick mode
    # keeps the 128-chip pod. The pod scale depends only on --full, not
    # on which TONS caches happen to exist.
    loaded = load_tons(512) if full else load_tons(128)
    topo = loaded[0] if loaded else \
        T.pdtt((8, 8, 8) if full else (4, 4, 8))
    lb_hops = None
    from repro.core.topology import bfs_all_pairs
    d = bfs_all_pairs(topo)
    lb_hops = d[np.isfinite(d)].sum() / (topo.n * (topo.n - 1))
    lb_load = R.load_lower_bound(topo)

    # Fig. 9: prioritization heuristics. The facade's per-stage timings
    # separate the admission front-end cost from the path-selection cost.
    results = {}
    for mode in ("apl", "random"):
        rp = route_pod(topo, PipelineConfig(
            priority=mode, K=4, engine="array",
            local_search_rounds=3, vc="none"))
        routed = rp.routed
        t_at, t_sel = rp.timings["at_s"], rp.timings["select_s"]
        results[mode] = (routed, rp.at)
        print(f"  {mode:6s}: Lmax/LB={routed.l_max / lb_load:.3f} "
              f"hops/min={routed.avg_hops / lb_hops:.3f} "
              f"AT={t_at:.2f}s select={t_sel:.2f}s")
        emit(f"fig9_at_time_{mode}", t_at * 1e6,
             f"{routed.l_max / lb_load:.3f}")
    # CPL: re-prioritize by the APL routing's chosen turn frequencies
    freq = R.turn_frequencies(results["apl"][0].table)
    rp = route_pod(topo, PipelineConfig(
        K=4, engine="array", local_search_rounds=3, vc="none"),
        chosen_loads=freq)
    routed_cpl = rp.routed
    t_at, t_sel = rp.timings["at_s"], rp.timings["select_s"]
    print(f"  cpl   : Lmax/LB={routed_cpl.l_max / lb_load:.3f} "
          f"hops/min={routed_cpl.avg_hops / lb_hops:.3f} "
          f"AT={t_at:.2f}s select={t_sel:.2f}s")
    emit("fig9_at_time_cpl", t_at * 1e6,
         f"{routed_cpl.l_max / lb_load:.3f}")
    emit("fig9_cpl_lmax_over_lb", 0,
         f"{routed_cpl.l_max / lb_load:.3f}")

    # Fig. 10: VC balance on TONS/AT
    at, routed = results["apl"][1], results["apl"][0]
    bal = allocate_vcs(at, routed.table.copy(), balance=True)
    unbal = allocate_vcs(at, routed.table.copy(), balance=False)
    print(f"  VC hops balanced={bal.tolist()} unbalanced={unbal.tolist()}")
    emit("fig10_vc_balance", 0,
         f"max/min={bal.max() / max(bal.min(), 1):.3f}")

    # Fig. 11: DOR skew on the torus baseline
    pt = T.pt((4, 4, 8))
    counts = NS.dor_paths(pt).vc_hop_counts()
    at_counts = route_pod(pt, PipelineConfig(
        K=4, engine="array", local_search_rounds=2,
        vc="inplace")).vc_counts
    print(f"  DOR hops/VC={counts.tolist()}  AT hops/VC="
          f"{at_counts.tolist()}")
    emit("fig11_dor_vc0_share", 0,
         f"{counts[0] / counts.sum():.3f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
