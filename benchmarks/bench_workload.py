"""Workload co-design tracking: demand-specialized synthesis vs the
demand-blind fabrics (the PR-10 headline).

For each registered workload (an a2a-heavy MoE arch and a ring-heavy
dense arch, both on the ``train_4k`` shape), measures at 128 chips
(``--full`` adds 256):

- wall-clock of ``synthesize_for_workload`` (the workload's
  translation-invariant demand weights riding into the symmetric
  synthesis LP as ``pair_weight``);
- the demand-weighted MCF and the trace-replay saturation
  (:func:`repro.core.workload.evaluate_workload`, routed through
  ``route_pod``) of the specialized fabric vs the generic
  uniform-demand TONS (``tons_<n>.pkl`` cache, skipped when absent)
  vs the PT torus -- both metrics must favor the specialized fabric;
- a two-tenant lane: the MoE and dense workloads composed onto one
  shared fabric (:func:`repro.core.traffic.compose_tenants`), swept
  through the CSR kernel with exact per-tenant packet conservation
  asserted and per-tenant delivered throughput recorded.

Specialized topologies are cached to
``benchmarks/results/tons_wl_<n>_<arch>.pkl`` so ``fig11_workload``
renders without re-synthesizing.

``--json`` writes BENCH_workload.json; guards warn -- and trip
``run.py --check`` -- when synthesis wall-clock exceeds 2x the stored
baseline, evaluation wall-clock exceeds 1.5x, or the
specialized-over-generic weighted-MCF advantage decays below 1/1.1 of
the stored ratio. All guards skip with a warning on a fresh checkout.
"""
from __future__ import annotations

import argparse
import json
import pickle
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks.common import (RESULTS, emit, guard_regression,
                               load_bench_json, load_tons)

WORKLOADS = [("deepseek-moe-16b", "train_4k"),    # MoE: a2a-heavy
             ("gemma-7b", "train_4k")]            # dense: ring-heavy
SPECS = [("n128", (4, 4, 8))]
FULL_SPECS = [("n256", (4, 8, 8))]
SYNTH_REGRESSION = 2.0   # single-shot synthesis wall guard (loose)
EVAL_REGRESSION = 1.5    # evaluation (route + LP + sweep) wall guard
QUALITY_REGRESSION = 1.1  # specialized/generic weighted-MCF ratio guard


def _evaluate(topo, wd, trace, sat_kwargs):
    # engine="array": demand-weighted selection (pair_weight) only
    # exists there, and evaluate_workload routes with the workload's
    # integer pair multiplicities by default.
    from repro.core import workload as W
    from repro.core.pipeline import PipelineConfig
    return W.evaluate_workload(
        topo, wd, trace=trace,
        cfg=PipelineConfig(K=4, engine="array", local_search_rounds=1),
        sat_kwargs=sat_kwargs)


def main(full: bool = False, json_path=None) -> dict:
    from repro.core import netsim as NS, topology as T, workload as W
    from repro.core.pipeline import PipelineConfig, route_pod
    from repro.core.traffic import compose_tenants

    prior = load_bench_json(json_path) if json_path else {}
    result: dict = {"K": 4, "select_engine": "array",
                    "weighted_routing": True, "sizes": {}}
    sat_kwargs = dict(step=0.02, cycles=2000, warmup=600)

    for sname, spec in SPECS + (FULL_SPECS if full else []):
        n = spec[0] * spec[1] * spec[2]
        generic = load_tons(n)
        pt_topo = T.pt(spec)
        size_row: dict = {"pod": list(spec), "workloads": {}}

        for arch, shape in WORKLOADS:
            wd = W.workload_demand(spec, arch, shape)
            trace = W.replay_trace(wd)
            t0 = time.time()
            res, _ = W.synthesize_for_workload(spec, arch, shape, wd=wd)
            t_synth = time.time() - t0
            sp_topo = res.to_topology()
            pkl = RESULTS / f"tons_wl_{n}_{arch}.pkl"
            pickle.dump({"optical": [list(e) for e in sp_topo.optical],
                         "arch": arch, "shape": shape,
                         "w_same_cube": wd.w_same_cube,
                         "w_ring": wd.w_ring,
                         "w_uniform": wd.w_uniform},
                        open(pkl, "wb"))

            t0 = time.time()
            ev_sp = _evaluate(sp_topo, wd, trace, sat_kwargs)
            ev_pt = _evaluate(pt_topo, wd, trace, sat_kwargs)
            ev_gn = _evaluate(generic[0], wd, trace, sat_kwargs) \
                if generic else None
            t_eval = time.time() - t0

            row = {
                "demand": {"w_same_cube": round(wd.w_same_cube, 4),
                           "w_ring": round(wd.w_ring, 4),
                           "w_uniform": round(wd.w_uniform, 4)},
                "synth_s": round(t_synth, 3),
                "eval_s": round(t_eval, 3),
                "lp_lambda": round(res.lp_lambda, 6) if res.lambdas
                else None,
                "specialized": ev_sp,
                "pt": ev_pt,
            }
            if ev_gn is not None:
                row["generic"] = ev_gn
                row["mcf_vs_generic"] = round(
                    ev_sp["weighted_mcf"]
                    / max(ev_gn["weighted_mcf"], 1e-12), 4)
                row["sat_vs_generic"] = round(
                    ev_sp["trace_saturation"]
                    / max(ev_gn["trace_saturation"], 1e-12), 4)
            row["mcf_vs_pt"] = round(
                ev_sp["weighted_mcf"]
                / max(ev_pt["weighted_mcf"], 1e-12), 4)
            row["sat_vs_pt"] = round(
                ev_sp["trace_saturation"]
                / max(ev_pt["trace_saturation"], 1e-12), 4)
            size_row["workloads"][arch] = row
            gen_txt = (f" generic={ev_gn['weighted_mcf']:.5f}"
                       f"/{ev_gn['trace_saturation']:.4f}"
                       if ev_gn else " generic=<no cache>")
            print(f"  {sname} {arch}: ws={wd.w_same_cube:.2f} "
                  f"wr={wd.w_ring:.2f} synth={t_synth:.1f}s")
            print(f"  {sname} {arch}: wMCF/sat specialized="
                  f"{ev_sp['weighted_mcf']:.5f}"
                  f"/{ev_sp['trace_saturation']:.4f}{gen_txt} "
                  f"pt={ev_pt['weighted_mcf']:.5f}"
                  f"/{ev_pt['trace_saturation']:.4f}")

            if json_path:
                prior_row = prior.get("sizes", {}).get(sname, {}) \
                    .get("workloads", {}).get(arch, {})
                guard_regression(f"workload_{sname}_{arch}_synth_s",
                                 t_synth, prior_row.get("synth_s"),
                                 SYNTH_REGRESSION)
                guard_regression(f"workload_{sname}_{arch}_eval_s",
                                 t_eval, prior_row.get("eval_s"),
                                 EVAL_REGRESSION)
                guard_regression(f"workload_{sname}_{arch}_mcf_vs_generic",
                                 row.get("mcf_vs_generic"),
                                 prior_row.get("mcf_vs_generic"),
                                 QUALITY_REGRESSION,
                                 larger_is_worse=False)

        # ---- two jobs, one fabric: per-tenant accounting -------------
        moe_arch, dense_arch = WORKLOADS[0][0], WORKLOADS[1][0]
        ta = W.workload_tenant("moe", spec, list(range(0, n // 2)),
                               moe_arch)
        tb = W.workload_tenant("dense", spec, list(range(n // 2, n)),
                               dense_arch, rate_share=0.5)
        tp = compose_tenants(n, [ta, tb])
        shared = generic[0] if generic else pt_topo
        tab = route_pod(shared, PipelineConfig(
            K=4, engine="sharded", local_search_rounds=1)).tables
        r = NS.sweep(tab, [0.1], traffic=tp, cycles=1500, warmup=500)[0]
        tens = r["tenants"]
        for tname, t in tens.items():
            assert t["injected"] == t["consumed"] + t["in_flight"], \
                f"tenant {tname} leaked packets"
        size_row["tenants"] = {
            "fabric": shared.name,
            "rate": 0.1,
            "per_tenant": {k: {kk: (round(vv, 5)
                                    if isinstance(vv, float) else vv)
                               for kk, vv in v.items()}
                           for k, v in tens.items()},
        }
        print(f"  {sname} tenants on {shared.name}: " + " ".join(
            f"{k}: inj={v['injected']} delivered={v['delivered']:.4f}"
            for k, v in tens.items()) + " (conservation exact)")
        result["sizes"][sname] = size_row

    r128 = result["sizes"]["n128"]["workloads"]
    for arch, _ in WORKLOADS:
        row = r128[arch]
        emit(f"bench_workload_{arch.split('-')[0]}_mcf_vs_pt", 0,
             f"{row['mcf_vs_pt']:.3f}x")
        if "mcf_vs_generic" in row:
            emit(f"bench_workload_{arch.split('-')[0]}_mcf_vs_generic",
                 row["synth_s"] * 1e6, f"{row['mcf_vs_generic']:.3f}x")
    if json_path:
        keep = "n256"                      # keep the --full record around
        prior_full = prior.get("sizes", {}).get(keep)
        if not full and prior_full and keep not in result["sizes"]:
            result["sizes"][keep] = prior_full
        Path(json_path).write_text(json.dumps(result, indent=2) + "\n")
        print(f"  wrote {json_path}")
    return result


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    main(args.full,
         json_path=Path(__file__).parent.parent / "BENCH_workload.json"
         if args.json else None)
