"""Fig. 5: uniform-random saturation points, normalized to best PT+DOR.

The injection-rate sweep runs as batched (lane-flattened) device
executions (`netsim.saturation_point`); pass ``traffic=`` for
non-uniform patterns.
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import emit, load_tons, timed


def saturation(topo, mode: str, step=0.02, cycles=3000, warmup=1000,
               seed=0, traffic=None, stats=None):
    from repro.core import netsim as NS
    from repro.core.pipeline import PipelineConfig, route_pod
    if mode == "dor":
        tab = NS.dor_tables(topo)          # 2 escape VCs (datelines)
    else:
        # Table 2: 4 VCs total; AT spreads turns over all of them
        tab = route_pod(topo, PipelineConfig(
            n_vc=4, K=4, seed=seed, engine="array",
            local_search_rounds=3)).tables
    sat, _ = NS.saturation_point(tab, step=step, cycles=cycles,
                                 warmup=warmup, traffic=traffic,
                                 stats=stats)
    return sat


def main(full: bool = False) -> None:
    from repro.core import topology as T
    spec = (4, 4, 8)
    step = 0.04 if not full else 0.01
    cyc = 2500 if not full else 6000

    results = {}
    sstats: dict = {}
    pt = T.pt(spec)
    results["PT+DOR"], us = timed(saturation, pt, "dor", step, cyc,
                                  stats=sstats)
    results["PT+AT"], _ = timed(saturation, pt, "at", step, cyc,
                                stats=sstats)
    pdtt = T.pdtt(spec)
    results["PDTT+AT"], _ = timed(saturation, pdtt, "at", step, cyc,
                                  stats=sstats)
    loaded = load_tons(128)
    if loaded:
        results["TONS+AT"], _ = timed(saturation, loaded[0], "at", step,
                                      cyc, stats=sstats)
    base = results["PT+DOR"]
    print("# saturation, normalized to PT+DOR (paper Fig. 5: TONS ~2x)")
    print(f"#  kernel={sstats.get('kernel')} peak sim array bytes "
          f"{sstats.get('array_bytes', 0):,}")
    for k, v in results.items():
        print(f"  {k:10s}: sat={v:.4f}  norm={v / base:.2f}x")
    if "TONS+AT" in results:
        emit("fig5_tons_over_pt", us,
             f"speedup={results['TONS+AT'] / base:.3f}x")
    emit("fig5_at_over_dor", us,
         f"speedup={results['PT+AT'] / base:.3f}x")

    # adaptive escape-VC lane: the same LP-balanced PDTT tables run
    # static and with occupancy-driven adaptivity, under the stress
    # patterns the static tables were not planned for (hotspot
    # concentration; synchronized mean-preserving injection bursts)
    from repro.core import netsim as NS
    from repro.core.pipeline import PipelineConfig, route_pod
    from repro.core.traffic import TrafficPattern
    tab = route_pod(pdtt, PipelineConfig(
        n_vc=4, priority="robust", K=4, local_search_rounds=1,
        engine="sharded", reserve_escape=True)).tables
    aspec = NS.adaptive_spec(pdtt)
    # hotspot saturation is consumption-limited (~= hot/(frac*n)), far
    # below the uniform grid -- each stress row carries its own grid
    stress = (
        ("hotspot", TrafficPattern.hotspot(pdtt.n, list(range(4)), 0.4),
         0.01, 0.12),
        ("bursty", TrafficPattern.uniform(pdtt.n).with_burst(
            64, duty=0.25, gain=3.0), step, 1.0),
    )
    print(f"# adaptive escape-VC routing vs static ({pdtt.name})")
    for pname, tp, pstep, pmax in stress:
        s, _ = NS.saturation_point(tab, step=pstep, max_rate=pmax,
                                   cycles=cyc, warmup=cyc // 3,
                                   traffic=tp)
        a, _ = NS.saturation_point(tab, step=pstep, max_rate=pmax,
                                   cycles=cyc, warmup=cyc // 3,
                                   traffic=tp, adaptive=aspec)
        print(f"  {pname:8s}: static={s:.4f} adaptive={a:.4f} "
              f"({a / max(s, 1e-9):.2f}x)")
        emit(f"fig5_adaptive_{pname}", 0,
             f"static={s:.4f} adaptive={a:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
