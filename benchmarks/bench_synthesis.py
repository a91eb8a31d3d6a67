"""Synthesis-at-scale tracking: batched LP topology synthesis, evaluated
end-to-end through the production routing stack.

For each pod size, measures:

- wall-clock of ``synthesize`` (vectorised LP build + batched greedy
  fixing with warm-started solves), the LP-relaxation lambda trajectory,
  and the final lambda against the Basu et al. theoretical upper bound
  (``mcf_upper_bound_basu``);
- the exact integral MCF of the synthesized topology (HiGHS metric LP)
  where affordable, vs the PT torus baseline -- the paper's Fig. 2/3
  story;
- the synthesized fabric routed end-to-end (``Channels.from_topology``
  -> ``allowed_turns`` -> ``select_paths(engine="sharded")`` -> VC alloc
  -> deadlock-free verify): routed ``l_max`` and netsim saturation
  throughput vs the same pipeline on the best-torus baseline.

Quick mode covers the 128-chip 4x4x8 pod; ``--full`` adds 4x8x8 (256)
and the 8^3 512-chip pod -- the scale the seed synthesis never reached.
Synthesized topologies are cached to ``benchmarks/results/tons_<n>.pkl``
so fig2/fig3/fig9 and the examples pick them up.

``--json`` writes BENCH_synthesis.json; prior results are loaded
tolerantly (guards skip with a warning on a fresh checkout) and
regression guards warn -- and trip ``run.py --check`` -- when synthesis
wall-clock exceeds 2x the stored baseline or the final LP lambda drops
below 1/1.1 of it.
"""
from __future__ import annotations

import argparse
import json
import pickle
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks.common import (RESULTS, emit, guard_regression,
                               load_bench_json)

SPECS = [("n128", (4, 4, 8))]
FULL_SPECS = [("n256", (4, 8, 8)), ("n512", (8, 8, 8))]
MCF_CAP = 256           # largest pod whose exact integral MCF we solve
SAT_CAP = 256           # largest pod simulated to saturation
SYNTH_REGRESSION = 2.0  # single-shot wall-clock guard (synthesis is too
                        # expensive to repeat 3x; use a loose bound)
LAMBDA_REGRESSION = 1.1  # quality guard on the final LP lambda


def _exact_mcf(topo, n_completed: int) -> float:
    """Integral MCF; the cube-translation reduction is only sound when
    the matching completion added no symmetry-breaking edges."""
    from repro.core import topology as T
    from repro.core.mcf import mcf_uniform
    perms = T.cube_translations(topo.pod) if n_completed == 0 else None
    lam, _ = mcf_uniform(topo.edges(), topo.n, perms=perms, prefer="highs")
    return float(lam)


def main(full: bool = False, json_path=None) -> dict:
    from repro.core import synthesis as SY, topology as T
    from repro.core.mcf import mcf_upper_bound_basu

    prior = load_bench_json(json_path) if json_path else {}
    result: dict = {"K": 4, "select_engine": "sharded", "sizes": {}}
    sat_kwargs = dict(step=0.02, cycles=2500, warmup=800)

    for name, spec in SPECS + (FULL_SPECS if full else []):
        n = spec[0] * spec[1] * spec[2]
        t0 = time.time()
        res = SY.synthesize(spec, symmetric=True)
        t_synth = time.time() - t0
        topo = res.to_topology()
        basu = mcf_upper_bound_basu(n)
        # None (JSON null) when every LP solve failed -- NaN would both
        # corrupt the JSON and sail through the quality guard
        lp_lambda = round(res.lp_lambda, 6) if res.lambdas else None
        row = {
            "pod": list(spec),
            "synth_s": round(t_synth, 3),
            "status": res.status,
            "lp_lambda": lp_lambda,
            "lp_rounds": len(res.lambdas),
            "interval": res.stats["interval"],
            "lp_n_var": res.stats["n_var"],
            "lp_build_s": res.stats["build_s"],
            "n_orbits": res.n_orbits,
            "n_fixed": res.n_fixed,
            "n_completed": res.n_completed,
            "basu_bound": round(basu, 6),
            "lambda_vs_basu": round(res.lp_lambda / basu, 4)
            if lp_lambda is not None else None,
        }
        print(f"  {name}: synth={t_synth:.1f}s lambda={res.lp_lambda:.5f} "
              f"({(row['lambda_vs_basu'] or float('nan')):.2f}x of Basu "
              f"bound "
              f"{basu:.5f}) fixed={res.n_fixed}/{res.n_orbits} orbits "
              f"+{res.n_completed} completion edges "
              f"({row['lp_rounds']} solves, interval={row['interval']})")

        mcf = None
        if n <= MCF_CAP:
            t0 = time.time()
            mcf = _exact_mcf(topo, res.n_completed)
            row["mcf"] = round(mcf, 6)
            row["mcf_s"] = round(time.time() - t0, 3)
            row["mcf_vs_basu"] = round(mcf / basu, 4)
            print(f"  {name}: integral mcf={mcf:.5f} "
                  f"({row['mcf_vs_basu']:.2f}x of Basu bound)")

        # ---- end-to-end: synthesized vs best-torus through the stack ----
        sat = (n <= SAT_CAP) if full else (n <= 128)
        ee = SY.evaluate_end_to_end(topo, K=4, select_engine="sharded",
                                    saturation=sat, sat_kwargs=sat_kwargs)
        row["synth_routed"] = ee
        pt_topo = T.pt(spec)
        pt = SY.evaluate_end_to_end(pt_topo, K=4, select_engine="sharded",
                                    saturation=sat, sat_kwargs=sat_kwargs)
        row["pt_routed"] = pt
        row["l_max_vs_pt"] = round(ee["l_max"] / max(pt["l_max"], 1e-9), 4)
        assert ee["deadlock_free"] and ee["unreachable"] == 0, \
            "synthesized pod must route deadlock-free"
        print(f"  {name}: routed l_max={ee['l_max']:.0f} vs "
              f"PT {pt['l_max']:.0f} ({row['l_max_vs_pt']:.2f}x, lower is "
              f"better) avg_hops {ee['avg_hops']:.2f}/{pt['avg_hops']:.2f} "
              f"e2e={ee['end_to_end_s']:.1f}s deadlock_free="
              f"{ee['deadlock_free']}")
        if sat and "saturation" in ee:
            ratio = ee["saturation"] / max(pt["saturation"], 1e-9)
            row["saturation_vs_pt"] = round(ratio, 3)
            print(f"  {name}: saturation {ee['saturation']:.4f} vs PT "
                  f"{pt['saturation']:.4f} ({ratio:.2f}x)")

        # cache for fig2/fig3/fig9 + the examples ("mcf" falls back to
        # the LP relaxation when the exact metric LP wasn't affordable)
        d, h = T.diameter_avg_hops(topo)
        pkl = RESULTS / f"tons_{n}.pkl"
        pickle.dump({"optical": [list(e) for e in topo.optical],
                     "lambdas": res.lambdas, "times": res.times,
                     "mcf": mcf if mcf is not None else res.lp_lambda,
                     "mcf_exact": mcf is not None,
                     "diam": d, "hops": h},
                    open(pkl, "wb"))
        row["diam"], row["hops"] = d, round(h, 4)
        print(f"  {name}: cached {pkl.name} (diam={d} hops={h:.3f})")

        if json_path:
            prior_row = prior.get("sizes", {}).get(name, {})
            guard_regression(f"synthesis_{name}_synth_s", t_synth,
                             prior_row.get("synth_s"), SYNTH_REGRESSION)
            # lp_lambda is None when synthesis failed -> trips the
            # missing-metric branch of the guard
            guard_regression(f"synthesis_{name}_lambda", lp_lambda,
                             prior_row.get("lp_lambda"), LAMBDA_REGRESSION,
                             larger_is_worse=False)
        result["sizes"][name] = row

    r128 = result["sizes"]["n128"]
    emit("bench_synthesis_n128", r128["synth_s"] * 1e6,
         f"lambda={r128['lp_lambda']}")
    if "mcf" in r128:
        emit("bench_synthesis_n128_mcf", 0, f"{r128['mcf']:.5f}")
    if json_path:
        for keep in ("n256", "n512"):       # keep the --full records around
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
         json_path=Path(__file__).parent.parent / "BENCH_synthesis.json"
         if args.json else None)
