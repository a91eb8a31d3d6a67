"""Fig. 6: all-gather / all-reduce / all-to-all schedule utilization."""
from __future__ import annotations

import argparse

from benchmarks.common import emit, load_tons, timed


def main(full: bool = False) -> None:
    from repro.core import collectives as C, topology as T
    from repro.core.mcf import mcf_uniform
    from repro.core.pipeline import PipelineConfig, route_pod

    cases = [("PT", T.pt((4, 4, 8)), 0.0078125)]
    loaded = load_tons(128)
    if loaded:
        cases.append(("TONS", loaded[0], loaded[1]["mcf"]))
    print("# collective utilization (paper Fig. 6: AG/AR near-ideal for "
          "all; TONS tracks a higher a2a MCF limit)")
    for name, topo, lam in cases:
        routed = route_pod(topo, PipelineConfig(
            K=4, engine="array", local_search_rounds=3,
            vc="none")).routed
        (rep, us) = timed(C.collective_report, topo, routed, lam)
        for kind, r in rep.items():
            print(f"  {name:5s} {kind:11s}: util={r['utilization']:.3f} "
                  f"(mcf-limit util={r['mcf_limit_utilization']:.3f})")
        emit(f"fig6_{name.lower()}_a2a", us,
             f"util={rep['all-to-all']['utilization']:.3f}")
        # effective a2a bandwidth for the framework's collective term
        bw = C.effective_a2a_bandwidth(lam, topo.n)
        print(f"  {name:5s} effective per-node a2a bw: {bw / 1e9:.1f} GB/s")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
