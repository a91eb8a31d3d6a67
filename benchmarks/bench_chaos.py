"""Chaos campaign benchmark: seeded multi-fault timeline at 8^3.

The lane builds the 512-chip serving configuration (PDTT fabric, robust
AT, n_vc=2, K=4 -- the same state the bench_routing repair lane and
tests/test_repair.py exercise), samples a >= 20-event fault/heal
schedule (storms with overlapping arrivals, correlated link groups
including a guaranteed node isolation served degraded, restorations,
and a final heal) and drives the state through it with
:func:`repro.core.chaos.run_campaign`. Every event's invariant suite
must come back green and the post-heal fabric must recover full
reachability with ``l_max`` within ``POST_HEAL_L_MAX`` of the cold
build it started from.

Guards (skip cleanly when BENCH_chaos.json has no baseline yet):
campaign wall-clock 1.5x vs the stored baseline, and the post-heal
l_max ratio against a fixed 1.0 baseline with the 1.10x quality bound.
``--full`` adds netsim throughput probes along the timeline (degraded
tables compacted through the CSR kernel, watchdog outputs included).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

from benchmarks.common import emit, guard_regression, load_bench_json

CAMPAIGN_REGRESSION = 1.5   # campaign wall-clock guard vs stored baseline
POST_HEAL_L_MAX = 1.10      # post-heal l_max quality bound vs cold build


def main(full: bool = False, json_path=None) -> dict:
    import numpy as np

    from repro.core import chaos as X, topology as T
    from repro.core.repair import ServingState

    prior = load_bench_json(json_path) if json_path else {}
    result: dict = {"campaign": {}}
    out = result["campaign"]
    for name, spec in [("n512", (8, 8, 8))]:
        topo = T.pdtt(spec)
        t0 = time.time()
        st = ServingState.build(topo, n_vc=2, K=4, seed=0, robust=True)
        t_build = time.time() - t0
        sched = X.generate_schedule(st.at, n_arrivals=20, seed=7)
        assert sched.n_events >= 20, sched.kinds()
        t0 = time.time()
        res = X.run_campaign(st, sched, coalesce=1.0,
                             probe_every=5 if full else 0)
        t_campaign = time.time() - t0

        # acceptance coverage: a coalesced storm, a degraded-mode event
        # (lost pairs served without cold recompute), a restoration, and
        # every invariant of every event green
        recs = res.records
        assert any(r.kind == "storm" and r.coalesced > 1 for r in recs)
        assert any(r.lost_pairs > 0 and not r.fallback for r in recs)
        assert any(r.kind == "restore" for r in recs)
        assert not any(r.fallback for r in recs)
        assert res.ok, [r.invariants for r in recs if not r.ok]
        # final heal recovered every pair
        assert len(res.state.lost) == 0
        assert res.state.table.n_routed() == res.state.table.n_flows
        ratio = float(res.state.l_max) / max(res.baseline_l_max, 1e-9)

        mttrs = np.array([r.mttr_s for r in recs])
        out[name] = {
            "pod": list(spec),
            "build_s": round(t_build, 3),
            "campaign_s": round(t_campaign, 3),
            "n_events": sched.n_events,
            "n_groups": len(recs),
            "kinds": sched.kinds(),
            "max_coalesced": max(r.coalesced for r in recs),
            "mttr_median_s": round(float(np.median(mttrs)), 3),
            "mttr_max_s": round(float(mttrs.max()), 3),
            "flows_rerouted": int(sum(r.flows_rerouted for r in recs)),
            "min_served_fraction": round(res.min_served_fraction, 6),
            "max_lost_pairs": max(r.lost_pairs for r in recs),
            "baseline_l_max": res.baseline_l_max,
            "post_heal_l_max": float(res.state.l_max),
            "post_heal_l_max_ratio": round(ratio, 4),
            "invariants_ok": res.ok,
        }
        if full:
            probes = [r.probe for r in recs if r.probe is not None]
            base = (res.baseline_probe or {}).get("delivered", 0.0)
            out[name]["probes"] = {
                "baseline": res.baseline_probe,
                "n_probes": len(probes),
                "min_throughput_retained": round(min(
                    (p["delivered"] / base for p in probes), default=1.0),
                    4) if base else None,
                "stalled_lanes": sum(p["stalled_at"] >= 0 for p in probes),
            }
        print(f"  {name}: campaign={t_campaign:.1f}s "
              f"(build={t_build:.1f}s) events={sched.n_events} "
              f"groups={len(recs)} kinds={sched.kinds()} "
              f"max_coalesced={out[name]['max_coalesced']} "
              f"mttr med/max={out[name]['mttr_median_s']:.2f}/"
              f"{out[name]['mttr_max_s']:.2f}s")
        print(f"        min served={res.min_served_fraction:.4f} "
              f"max lost={out[name]['max_lost_pairs']} "
              f"post-heal lmax {res.state.l_max:.0f}/"
              f"{res.baseline_l_max:.0f} ({ratio:.3f}x) "
              f"invariants={'green' if res.ok else 'RED'}")

    n512 = out["n512"]
    emit("bench_chaos_n512", n512["campaign_s"] * 1e6,
         f"events={n512['n_events']} "
         f"min_served={n512['min_served_fraction']:.4f} "
         f"ratio={n512['post_heal_l_max_ratio']:.3f}")
    if json_path:
        prior_c = prior.get("campaign", {}).get("n512", {})
        guard_regression("chaos_n512_campaign_s", n512["campaign_s"],
                         prior_c.get("campaign_s"), CAMPAIGN_REGRESSION)
        # quality guard: fixed 1.0 baseline -> trips when the healed
        # fabric's l_max drifts past POST_HEAL_L_MAX x the cold build
        guard_regression("chaos_n512_post_heal_l_max_ratio",
                         n512["post_heal_l_max_ratio"], 1.0,
                         POST_HEAL_L_MAX)
        if not full and "probes" in prior_c and "probes" not in n512:
            n512["probes"] = prior_c["probes"]   # keep the --full record
        import json
        Path(json_path).write_text(json.dumps(result, indent=2) + "\n")
        print(f"  wrote {json_path}")
    return result


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", action="store_true")
    a = ap.parse_args()
    main(a.full,
         json_path=Path(__file__).parent.parent / "BENCH_chaos.json"
         if a.json else None)
