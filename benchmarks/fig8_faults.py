"""Fig. 8: saturation under all 48 single-OCS faults (robust AT routing).

Quick mode scores every fault analytically (1/L_max of the re-routed
tables) and simulates a few representative faults; --full simulates all.
Each fault is recovered **both ways** -- full re-selection against the
masked AT (the paper's fault-specific tables) and the incremental
:func:`repro.core.repair.repair_fault` from a live serving state -- and
the wall clocks are reported side by side. Each simulated fault then
runs both recovered tables twice: uniform traffic, and the adversarial
fault-correlated pattern (recovery demand concentrated on the nodes that
just lost links, boosted injection inside the region), so the repaired
fabric's post-recovery saturation sits next to the recomputed one."""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import emit, load_tons, timed


def main(full: bool = False) -> None:
    from repro.core import collectives as C, fault as F, netsim as NS, \
        topology as T
    from repro.core.pipeline import PipelineConfig, route_pod
    from repro.core.repair import ServingState, repair_fault
    from repro.core.routing import RoutingResult
    from repro.core.traffic import TrafficPattern

    cases = [("PDTT", T.pdtt((4, 4, 8)))]
    loaded = load_tons(128)
    if loaded:
        cases.append(("TONS", loaded[0]))

    import time

    for name, topo in cases:
        cfg = PipelineConfig(n_vc=4, robust=True, K=4,
                             local_search_rounds=2, vc="none")
        rp = route_pod(topo, cfg)
        at, base = rp.at, rp.routed
        # the live fabric the incremental repairs recover from
        st = ServingState.build(topo, n_vc=4, K=4, seed=0, robust=True)
        colors = F.colors_in_use(topo)
        lmaxes, rep_lmaxes = [], []
        disconnected = 0
        sims = {}
        sim_colors = colors[:: max(1, len(colors) // 4)] if not full \
            else colors
        t_route = 0.0
        t_repair = 0.0
        flows_rerouted = 0
        sstats: dict = {}

        def saturate(tables, rres, dead_region):
            traffic = C.a2a_traffic(rres)
            sat, _ = NS.saturation_point(tables, step=0.05, cycles=2000,
                                         warmup=800, traffic=traffic,
                                         stats=sstats)
            fc = TrafficPattern.fault_correlated(topo.n, dead_region,
                                                 frac=0.5)
            sat_fc, _ = NS.saturation_point(tables, step=0.05, cycles=2000,
                                            warmup=800, traffic=fc,
                                            stats=sstats)
            return sat, sat_fc

        for color in colors:
            dead = F.dead_channels_for_color(at, color)
            t0 = time.time()
            routed = route_pod(
                topo, PipelineConfig(K=4, local_search_rounds=1,
                                     vc="none"),
                at=at, dead_channels=dead).routed
            t_route += time.time() - t0
            t0 = time.time()
            rr = repair_fault(st, dead)
            t_repair += time.time() - t0
            flows_rerouted += rr.flows_rerouted
            if routed.unreachable:
                disconnected += 1
                continue
            lmaxes.append(routed.l_max)
            rep_lmaxes.append(rr.l_max)
            if color in sim_colors:
                region = F.fault_region_nodes(at, color)
                tab = NS.at_tables(topo, at, routed)
                rst = rr.state
                rrouted = RoutingResult(
                    rst.table, rst.loads[:-1].astype(np.float64),
                    float(rr.l_max), rst.table.avg_hops(),
                    rr.unreachable)
                rtab = NS.at_tables(topo, rst.at, rrouted, balance=None)
                sims[color] = (saturate(tab, routed, region),
                               saturate(rtab, rrouted, region))
        lmaxes = np.array(lmaxes)
        rep_lmaxes = np.array(rep_lmaxes)
        print(f"  {name}: faults={len(colors)} disconnected={disconnected}"
              f" analytic 1/Lmax: no-fault={1 / base.l_max:.5f} "
              f"min={1 / lmaxes.max():.5f} med={1 / np.median(lmaxes):.5f}"
              f" ({t_route:.1f}s to re-route all faults, array engine)")
        print(f"        incremental repair: {t_repair:.1f}s for all "
              f"faults ({t_route / max(t_repair, 1e-9):.0f}x faster, "
              f"{flows_rerouted} flows re-routed total) "
              f"repaired 1/Lmax: min={1 / rep_lmaxes.max():.5f} "
              f"med={1 / np.median(rep_lmaxes):.5f} "
              f"worst ratio={float((rep_lmaxes / lmaxes).max()):.3f}x")
        if sims:
            print(f"        simulated saturations "
                  f"(recomputed | repaired, uniform/fault-correlated): "
                  + " ".join(
                      f"c{c}={u:.3f}/{fcv:.3f}|{ru:.3f}/{rfc:.3f}"
                      for c, ((u, fcv), (ru, rfc)) in sims.items()))
            print(f"        sim kernel={sstats.get('kernel')} peak array "
                  f"bytes {sstats.get('array_bytes', 0):,}")
        # mid-sweep fault: the OCS dies at cycle t *while packets are in
        # flight* -- no chance to preload fault-specific tables. Static
        # tables strand every packet whose frozen path died; the
        # adaptive escape-VC kernel re-resolves them onto surviving
        # alternates or the re-rooted escape tree, conserving both ways.
        color0 = sim_colors[0]
        ev = F.fault_event(at, color0, 800)
        atab = NS.at_tables(topo, at, base, reserve_escape=True)
        aspec = NS.adaptive_spec(topo, dead_channels=ev[1])
        wstats: dict = {}
        stt = NS.sweep(atab, [0.1], cycles=2000, warmup=800,
                       fault=ev, stats=wstats)[0]
        st_cycles = wstats.get("cycles_run")
        adt = NS.sweep(atab, [0.1], cycles=2000, warmup=800, fault=ev,
                       adaptive=aspec, stats=wstats)[0]
        print(f"        mid-sweep fault c{color0}@800: stranded "
              f"in-flight static={stt['in_flight']} "
              f"adaptive={adt['in_flight']} "
              f"(escaped={adt['escaped']}, watchdog "
              f"{'quiet' if adt['stalled_at'] < 0 else 'FIRED'})")
        # watchdog outputs, surfaced: the cycle each lane's livelock
        # watchdog fired (-1 = never) and the cycles the kernels ran
        # (static strands packets but must not wedge the whole lane)
        print(f"        watchdog: static stalled_at={stt['stalled_at']} "
              f"cycles_run={st_cycles} | adaptive "
              f"stalled_at={adt['stalled_at']} "
              f"cycles_run={wstats.get('cycles_run')}")
        emit(f"fig8_{name.lower()}_midsweep", 0,
             f"static_stranded={stt['in_flight']} "
             f"adaptive_stranded={adt['in_flight']}")
        emit(f"fig8_{name.lower()}_watchdog", 0,
             f"static_stalled_at={stt['stalled_at']} "
             f"adaptive_stalled_at={adt['stalled_at']} "
             f"cycles_run={wstats.get('cycles_run')}")
        emit(f"fig8_{name.lower()}", 0,
             f"worst_fault_frac={base.l_max / lmaxes.max():.3f}")
        emit(f"fig8_{name.lower()}_repair", t_repair * 1e6,
             f"speedup={t_route / max(t_repair, 1e-9):.1f}x "
             f"worst_ratio={float((rep_lmaxes / lmaxes).max()):.3f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
