"""Benchmark suite entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus human-readable detail).
Quick settings by default; pass --full for the paper-scale sweeps.

A suite that raises makes the run exit 2, with or without ``--check``.
CI usage: ``python benchmarks/run.py --json --check`` runs every suite,
writes the BENCH_*.json trackers, and also exits 1 when a regression
guard trips. Guards compare against the stored BENCH_*.json baselines
and skip with a warning when those are absent (fresh checkout / fork),
so a first CI run always passes the guard stage.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="also write machine-readable BENCH_netsim.json "
                         "(netsim sweep wall-clock + per-pattern "
                         "saturation points, the guarded 8^3 CSR-kernel "
                         "section with staged array bytes + peak RSS, "
                         "and with --full the 12^3 n1728 saturation "
                         "entry -- kept across quick runs, guards skip "
                         "while it is missing), BENCH_routing.json "
                         "(routing-engine wall-clock at 64/256/512 chips "
                         "incl. the batched allowed-turns admission "
                         "breakdown, per-stage select splits for the "
                         "array and streaming sharded engines, and VC "
                         "greedy-dead-end counters; the guarded 8^3 "
                         "time-to-recover lane -- single-OCS repair wall "
                         "clock, flows re-routed and post-repair l_max "
                         "ratio vs the full-recompute oracle; with "
                         "--full also the "
                         "1728-chip 12^3 and 4096-chip 16^3 end-to-end "
                         "entries routed by the sharded engine into the "
                         "CSR PathTable plus the 12^3 repair entry) and "
                         "BENCH_synthesis.json "
                         "(batched LP synthesis wall-clock, lambda vs "
                         "the Basu bound, routed l_max + saturation of "
                         "synthesized vs torus pods; --full adds the "
                         "256-chip and 8^3 512-chip entries) and "
                         "BENCH_chaos.json (the guarded 8^3 chaos "
                         "campaign: >= 20-event seeded fault/heal "
                         "timeline wall-clock with per-event invariant "
                         "checks, min served-pair fraction and the "
                         "post-heal l_max ratio vs the cold build; "
                         "--full adds netsim throughput probes along "
                         "the timeline) and BENCH_workload.json (the "
                         "guarded workload co-design lane: per-workload "
                         "demand-specialized synthesis wall-clock, "
                         "demand-weighted MCF + trace-replay saturation "
                         "of specialized vs generic TONS vs torus, and "
                         "the two-tenant shared-fabric accounting; "
                         "--full adds the 256-chip entry). Guarded "
                         "timings are medians of 3 repeats; regressions "
                         "past the per-guard bound vs the stored "
                         "baseline print a WARNING line")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when any regression guard trips -- the "
                         "CI regression-guard mode; guards skip cleanly "
                         "when no BENCH_*.json baseline exists yet")
    args = ap.parse_args()
    if args.check and not args.json:
        # guards compare against (and refresh) the BENCH_*.json
        # baselines; --check without them would silently check nothing
        print("## --check implies --json (guards need the stored "
              "baselines)")
        args.json = True

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_chaos, bench_netsim, bench_routing,
                            bench_synthesis, bench_workload,
                            fig1_smallgraphs, fig2_progress,
                            fig3_analytical, fig5_saturation,
                            fig6_collectives, fig7_traces, fig8_faults,
                            fig9_routing_ablation, fig10_chaos,
                            fig11_workload, roofline)
    from benchmarks.common import REGRESSIONS
    root = Path(__file__).parent.parent
    netsim_json = root / "BENCH_netsim.json" if args.json else None
    routing_json = root / "BENCH_routing.json" if args.json else None
    synthesis_json = root / "BENCH_synthesis.json" if args.json else None
    chaos_json = root / "BENCH_chaos.json" if args.json else None
    workload_json = root / "BENCH_workload.json" if args.json else None
    suites = [
        ("fig1_smallgraphs", fig1_smallgraphs.main),
        ("fig2_progress", fig2_progress.main),
        ("fig3_analytical", fig3_analytical.main),
        ("fig5_saturation", fig5_saturation.main),
        ("fig6_collectives", fig6_collectives.main),
        ("fig7_traces", fig7_traces.main),
        ("fig8_faults", fig8_faults.main),
        ("fig9_routing_ablation", fig9_routing_ablation.main),
        ("fig10_chaos", fig10_chaos.main),
        ("roofline", roofline.main),
        ("bench_netsim",
         lambda full=False: bench_netsim.main(full, json_path=netsim_json)),
        ("bench_routing",
         lambda full=False: bench_routing.main(full,
                                               json_path=routing_json)),
        ("bench_synthesis",
         lambda full=False: bench_synthesis.main(
             full, json_path=synthesis_json)),
        ("bench_chaos",
         lambda full=False: bench_chaos.main(full, json_path=chaos_json)),
        ("bench_workload",
         lambda full=False: bench_workload.main(
             full, json_path=workload_json)),
        ("fig11_workload", fig11_workload.main),
    ]
    errors = []
    print("name,us_per_call,derived")
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        print(f"## {name}")
        t0 = time.time()
        try:
            fn(full=args.full)
        except Exception as e:
            print(f"{name},0,ERROR:{e}")
            traceback.print_exc()
            errors.append(name)
        print(f"## {name} done in {time.time() - t0:.1f}s", flush=True)

    if errors:
        print(f"## suites with errors: {', '.join(errors)}")
    if REGRESSIONS:
        print(f"## regression guards tripped: "
              f"{', '.join(g['name'] for g in REGRESSIONS)}")
    if errors:
        return 2
    if args.check and REGRESSIONS:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
