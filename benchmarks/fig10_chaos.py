"""Fig. 10: availability over a chaos-campaign timeline.

A 128-chip PDTT serving state rides a seeded fault/heal schedule
(storms, correlated link groups with a guaranteed node isolation,
restorations, final heal) and every repair group is followed by a
netsim throughput probe of the degraded fabric (lost pairs compacted
out of the CSR table). The figure is the timeline table: served-pair
fraction and throughput retained vs the healthy baseline at every
event, alongside MTTR, flows re-routed and the post-event l_max --
the degraded-mode serving story end to end. ``--full`` lengthens the
campaign and the probes."""
from __future__ import annotations

import argparse

from benchmarks.common import emit


def main(full: bool = False) -> None:
    from repro.core import chaos as X, topology as T
    from repro.core.repair import ServingState

    topo = T.pdtt((4, 4, 8))
    st = ServingState.build(topo, n_vc=4, K=4, seed=0, robust=True)
    sched = X.generate_schedule(st.at, n_arrivals=16 if full else 10,
                                seed=3)
    res = X.run_campaign(st, sched, coalesce=1.0, probe_every=1,
                         probe_rate=0.05,
                         probe_cycles=2000 if full else 1200,
                         probe_warmup=800 if full else 400)
    assert res.ok, [r.invariants for r in res.records if not r.ok]

    base = (res.baseline_probe or {}).get("delivered", 0.0)
    print(f"  PDTT 128: events={sched.n_events} groups="
          f"{len(res.records)} kinds={sched.kinds()} baseline "
          f"lmax={res.baseline_l_max:.0f} delivered={base:.4f}")
    print("        t      kind     chans coal  mttr_s  flows  lost "
          "served   lmax  tput_ret")
    for r in res.records:
        ret = (r.probe["delivered"] / base
               if r.probe is not None and base else float("nan"))
        print(f"   {r.t:8.1f} {r.kind:>8s} {r.n_channels:5d} "
              f"{r.coalesced:4d} {r.mttr_s:7.3f} {r.flows_rerouted:6d} "
              f"{r.lost_pairs:5d} {r.served_fraction:6.4f} "
              f"{r.l_max:6.0f} {ret:9.4f}")
    final = res.records[-1]
    rets = [r.probe["delivered"] / base for r in res.records
            if r.probe is not None and base]
    print(f"        final: served={final.served_fraction:.4f} "
          f"lost={len(res.state.lost)} post-heal lmax "
          f"{res.state.l_max:.0f}/{res.baseline_l_max:.0f} "
          f"min tput retained={min(rets, default=1.0):.4f}")
    emit("fig10_chaos", 0,
         f"min_served={res.min_served_fraction:.4f} "
         f"min_tput_retained={min(rets, default=1.0):.4f} "
         f"final_served={final.served_fraction:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
