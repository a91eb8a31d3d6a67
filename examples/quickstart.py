"""Quickstart: synthesize a TONS topology, route it deadlock-free, and
compare its throughput proxy against the production torus baselines.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import numpy as np

from repro.core import synthesis as SY, topology as T
from repro.core.mcf import mcf_uniform, mcf_topology
from repro.core.pipeline import PipelineConfig, route_pod


def main() -> None:
    spec = (4, 4, 8)  # 128 chips = 2 cubes: the smallest interesting pod

    print("== baselines ==")
    pt = T.pt(spec)
    lam_pt, _ = mcf_uniform(pt.edges(), pt.n,
                            perms=T.torus_translations(pt.pod),
                            prefer="highs")
    pdtt = T.pdtt(spec)
    lam_pdtt, _ = mcf_uniform(
        pdtt.edges(), pdtt.n,
        perms=T.torus_translations(pdtt.pod, twisted=True), prefer="highs")
    print(f"PT   {spec}: MCF = {lam_pt:.5f}")
    print(f"PDTT {spec}: MCF = {lam_pdtt:.5f}")

    print("== TONS synthesis (Algorithm 3, symmetric, interval=4) ==")
    res = SY.synthesize(spec, symmetric=True, interval=4, verbose=True)
    lam = mcf_topology(res.topology, prefer="highs")
    print(f"TONS {spec}: MCF = {lam:.5f} "
          f"({lam / lam_pt:.2f}x PT, {lam / lam_pdtt:.2f}x PDTT)")

    print("== deadlock-free routing within 2 VCs ==")
    rp = route_pod(res.topology, PipelineConfig(
        robust=True, K=4, engine="array", local_search_rounds=3,
        vc="inplace", verify=True))
    assert rp.deadlock_free
    print(f"all {rp.table.n_routed()} pairs routed; "
          f"L_max={rp.l_max:.0f} "
          f"(MCF bound {1 / lam:.0f}); "
          f"VC hop balance={rp.vc_counts.tolist()}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
