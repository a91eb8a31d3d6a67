"""Netsim perf tracking: batched sweep vs the seed's sequential sweep,
plus the CSR-native kernel at the scales the dense layout cannot stage.

Measures:

- on a 4x4x4 pod (one cube, 64 chips, PT wiring + DOR routing):
  wall-clock of the *seed's* sequential `saturation_point` (its original
  4-array kernel, vendored below as a frozen baseline; one jit call per
  rate with early exit) vs the current batched two-stage sweep, plus the
  current kernel driven sequentially, and the speedups; saturation
  points for the built-in traffic patterns (uniform, transpose, hotspot,
  demand-derived), all through the same jitted CSR kernel;
- on an 8^3 pod (512 chips): the guarded CSR section -- batched-sweep
  wall-clock (median of 3, 1.5x guard), staged array bytes of the CSR vs
  dense kernels (the CSR bytes carry a 1.15x guard: route tables are
  deterministic, so the staged working set must not creep), saturation,
  and process peak RSS;
- with ``--full``, the 12^3 (1728-chip) entry: route via the sharded
  engine, then the first saturation sweep at that scale -- dense
  ``(n, n, MAXHOP)`` tables would need ~1.7 GB before the first cycle;
  the CSR kernel stages O(total routed hops). The n1728 record is kept
  across non-full runs (like bench_routing's full-scale rows), and
  guards skip when the baseline is missing (fresh checkout / first run).

``--json`` (or ``main(json_path=...)``) writes BENCH_netsim.json so the
perf trajectory is tracked from PR to PR.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks.common import (emit, guard_regression, load_bench_json,
                               median_timed, peak_rss_mb)

SPEC = (4, 4, 4)
GUARD_SPEC = (8, 8, 8)          # 512 chips: the guarded CSR section
FULL_SPEC = (12, 12, 12)        # 1728 chips: --full saturation entry
SWEEP_REGRESSION = 1.5          # 8^3 batched-sweep wall-clock guard
BYTES_REGRESSION = 1.15         # 8^3 staged-array-bytes guard (deterministic)
ADAPTIVE_OFF_REGRESSION = 1.10  # adaptive-off path vs pre-adaptive baseline


# ---------------------------------------------------------------------------
# Frozen copy of the seed's simulator kernel (PR-0 netsim._simulate) used
# as the perf baseline. Do not modernise: its job is to stay fixed.
# ---------------------------------------------------------------------------


def _seed_simulate_factory():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("n", "n_ch", "n_vc", "slots",
                                       "cycles", "flits"))
    def _simulate(ch_dst, path, vcs, rate, key, *, n, n_ch, n_vc, slots,
                  cycles, warmup, flits=1):
        NQ = n_ch * n_vc
        q_src = jnp.zeros((NQ, slots), jnp.int32)
        q_dst = jnp.zeros((NQ, slots), jnp.int32)
        q_hop = jnp.zeros((NQ, slots), jnp.int32)
        head = jnp.zeros((NQ,), jnp.int32)
        size = jnp.zeros((NQ,), jnp.int32)
        rr = jnp.zeros((n_ch,), jnp.int32)
        busy = jnp.zeros((n_ch,), jnp.int32)

        def qid(c, v):
            return c * n_vc + v

        def cycle(i, carry):
            (q_src, q_dst, q_hop, head, size, rr, busy, key, stats) = carry
            offered, accepted, delivered = stats
            hs = q_src[jnp.arange(NQ), head]
            hd = q_dst[jnp.arange(NQ), head]
            hh = q_hop[jnp.arange(NQ), head]
            nonempty = size > 0
            arrive_node = ch_dst[jnp.arange(NQ) // n_vc]
            consume = nonempty & (arrive_node == hd)
            nxt_c = path[hs, hd, hh + 1]
            nxt_v = vcs[hs, hd, hh + 1].astype(jnp.int32)
            tq = jnp.where(consume, -1, qid(nxt_c, nxt_v))
            fwd_ok = nonempty & ~consume & (size[jnp.clip(tq, 0, NQ - 1)]
                                            < slots)
            eligible = consume | fwd_ok
            eligible = eligible & jnp.repeat(busy == 0, n_vc)
            elig_cv = eligible.reshape(n_ch, n_vc)
            offs = (rr[:, None] + jnp.arange(n_vc)[None, :]) % n_vc
            pri = jnp.take_along_axis(elig_cv, offs, axis=1)
            first = jnp.argmax(pri, axis=1)
            any_e = pri.any(axis=1)
            win_v = (rr + first) % n_vc
            win_q = jnp.arange(n_ch) * n_vc + win_v
            win_valid = any_e
            rr = jnp.where(win_valid, (win_v + 1) % n_vc, rr)
            w_src = hs[win_q]
            w_dst = hd[win_q]
            w_hop = hh[win_q]
            w_consume = consume[win_q] & win_valid
            w_target = jnp.where(win_valid & ~w_consume, tq[win_q], -1)
            sort_i = jnp.argsort(jnp.where(w_target < 0, NQ + 1, w_target))
            st = jnp.where(w_target < 0, NQ + 1, w_target)[sort_i]
            newgrp = jnp.concatenate([jnp.ones(1, bool), st[1:] != st[:-1]])
            grp_start = jnp.where(newgrp, jnp.arange(n_ch), 0)
            grp_start = jax.lax.associative_scan(jnp.maximum, grp_start)
            rank_sorted = jnp.arange(n_ch) - grp_start
            rank = jnp.zeros(n_ch, jnp.int32).at[sort_i].set(
                rank_sorted.astype(jnp.int32))
            space_ok = (size[jnp.clip(w_target, 0, NQ - 1)] + rank) < slots
            w_push = win_valid & ~w_consume & (w_target >= 0) & space_ok
            w_pop = w_consume | w_push
            busy = jnp.where(w_pop, flits - 1, jnp.maximum(busy - 1, 0))
            popq = jnp.where(w_pop, win_q, NQ)
            head = head.at[jnp.clip(popq, 0, NQ - 1)].add(
                jnp.where(w_pop, 1, 0)) % slots
            size = size.at[jnp.clip(popq, 0, NQ - 1)].add(
                jnp.where(w_pop, -1, 0))
            tgt = jnp.clip(w_target, 0, NQ - 1)
            slot = (head[tgt] + size[tgt] + rank) % slots
            q_src = q_src.at[tgt, slot].set(
                jnp.where(w_push, w_src, q_src[tgt, slot]))
            q_dst = q_dst.at[tgt, slot].set(
                jnp.where(w_push, w_dst, q_dst[tgt, slot]))
            q_hop = q_hop.at[tgt, slot].set(
                jnp.where(w_push, w_hop + 1, q_hop[tgt, slot]))
            size = size.at[tgt].add(jnp.where(w_push, 1, 0))
            key, k1, k2 = jax.random.split(key, 3)
            want = jax.random.uniform(k1, (n,)) < rate
            dsts = jax.random.randint(k2, (n,), 0, n - 1)
            srcs = jnp.arange(n)
            dsts = jnp.where(dsts >= srcs, dsts + 1, dsts)
            c0 = path[srcs, dsts, 0]
            v0 = vcs[srcs, dsts, 0].astype(jnp.int32)
            iq = qid(c0, v0)
            has_space = size[iq] < slots
            inj = want & has_space
            slot = (head[iq] + size[iq]) % slots
            q_src = q_src.at[iq, slot].set(
                jnp.where(inj, srcs, q_src[iq, slot]))
            q_dst = q_dst.at[iq, slot].set(
                jnp.where(inj, dsts, q_dst[iq, slot]))
            q_hop = q_hop.at[iq, slot].set(
                jnp.where(inj, 0, q_hop[iq, slot]))
            size = size.at[iq].add(jnp.where(inj, 1, 0))
            measure = i >= warmup
            offered = offered + jnp.where(measure, want.sum(), 0)
            accepted = accepted + jnp.where(measure, inj.sum(), 0)
            delivered = delivered + jnp.where(measure, w_consume.sum(), 0)
            return (q_src, q_dst, q_hop, head, size, rr, busy, key,
                    (offered, accepted, delivered))

        stats0 = (jnp.zeros((), jnp.int32),) * 3
        carry = (q_src, q_dst, q_hop, head, size, rr, busy, key, stats0)
        carry = jax.lax.fori_loop(0, cycles, cycle, carry)
        offered, accepted, delivered = carry[-1]
        return offered, accepted, delivered

    return _simulate


def _seed_sequential_saturation(tab, step, max_rate, cycles, warmup,
                                slots=128, flits=4, deficit=0.05):
    """The seed's `saturation_point`: python loop of per-rate jit calls on
    the frozen seed kernel, early exit at the first deficit."""
    import jax
    import jax.numpy as jnp

    sim = _seed_simulate_factory()
    meas = cycles - warmup
    sat, trace, rate = 0.0, [], step
    while rate <= max_rate + 1e-9:
        off, acc, dlv = sim(
            jnp.asarray(tab.ch_dst), jnp.asarray(tab.path),
            jnp.asarray(tab.vcs), jnp.float32(rate),
            jax.random.PRNGKey(0), n=tab.n, n_ch=tab.n_ch,
            n_vc=tab.n_vc, slots=slots, cycles=cycles, warmup=warmup,
            flits=flits)
        r = {"offered": float(off) / meas / tab.n,
             "delivered": float(dlv) / meas / tab.n, "rate": rate}
        trace.append(r)
        if r["delivered"] >= (1 - deficit) * r["offered"]:
            sat = r["delivered"]
        else:
            break
        rate += step
    return sat, trace


def main(full: bool = False, json_path=None) -> dict:
    import numpy as np

    from repro.core import netsim as NS, topology as T
    from repro.core.demand import WorkloadDemand
    from repro.core.traffic import TrafficPattern

    step = 0.02 if not full else 0.01
    cycles = 2500 if not full else 6000
    warmup = 800 if not full else 2000
    topo = T.pt(SPEC)
    tab = NS.dor_tables(topo)
    n = topo.n
    uniform = TrafficPattern.uniform(n)

    # warm every jit cache so the timings measure execution, not compile
    _seed_sequential_saturation(tab, 0.3, 0.3, cycles, warmup)
    NS.run(tab, step, traffic=uniform, cycles=cycles, warmup=warmup)
    NS.saturation_point(tab, step=step, cycles=cycles, warmup=warmup,
                        traffic=uniform)

    t0 = time.time()
    sat_seed, trace_seed = _seed_sequential_saturation(
        tab, step, 1.0, cycles, warmup)
    t_seed = time.time() - t0

    t0 = time.time()
    ct = uniform.compiled()
    sat_seq, rate = 0.0, step
    n_seq = 0
    while rate <= 1.0 + 1e-9:
        r = NS.run(tab, rate, traffic=ct, cycles=cycles, warmup=warmup)
        n_seq += 1
        if r["delivered"] >= 0.95 * r["offered"]:
            sat_seq = r["delivered"]
        else:
            break
        rate += step
    t_seq = time.time() - t0

    t0 = time.time()
    sat_batch, _ = NS.saturation_point(tab, step=step, cycles=cycles,
                                       warmup=warmup, traffic=uniform)
    t_batch = time.time() - t0

    speedup = t_seed / max(t_batch, 1e-9)
    print(f"  sweep wall-clock: seed-sequential({len(trace_seed)} rates)="
          f"{t_seed:.2f}s  current-sequential({n_seq} rates)={t_seq:.2f}s"
          f"  batched={t_batch:.2f}s -> {speedup:.1f}x vs seed")
    emit("bench_netsim_sweep_speedup", t_batch * 1e6, f"{speedup:.2f}x")

    wd = WorkloadDemand(topo.pod, w_same_cube=2.0, w_ring=2.0,
                        w_uniform=0.25)
    patterns = [uniform, TrafficPattern.transpose(topo.pod),
                TrafficPattern.hotspot(n, list(range(4)), 0.4),
                TrafficPattern.from_demand(wd)]
    sats = {}
    for pat in patterns:
        sat, _ = NS.saturation_point(tab, step=step, cycles=cycles,
                                     warmup=warmup, traffic=pat)
        sats[pat.name] = sat
        print(f"  saturation[{pat.name:10s}] = {sat:.4f}")
    emit("bench_netsim_uniform_sat", 0, f"{sats['uniform']:.4f}")

    result = {
        "pod": list(SPEC),
        "rate_step": step,
        "cycles": cycles,
        "sweep_seed_sequential_s": round(t_seed, 4),
        "sweep_current_sequential_s": round(t_seq, 4),
        "sweep_batched_s": round(t_batch, 4),
        "sweep_speedup_vs_seed": round(speedup, 2),
        "saturation_uniform_seed_kernel": round(sat_seed, 5),
        "saturation": {k: round(v, 5) for k, v in sats.items()},
    }
    prior = load_bench_json(json_path) if json_path else {}

    # ---- guarded 8^3 CSR section -------------------------------------
    topo8 = T.pt(GUARD_SPEC)
    tab8 = NS.dor_tables(topo8)
    rates8 = [0.05, 0.1, 0.2, 0.4]
    s_csr: dict = {}
    s_dense: dict = {}
    NS.sweep(tab8, rates8, cycles=1500, warmup=500, stats=s_csr)  # warm jit
    trace8, t_sweep8 = median_timed(
        lambda: NS.sweep(tab8, rates8, cycles=1500, warmup=500,
                         stats=s_csr), repeats=3)
    NS.sweep(tab8, rates8[:1], cycles=200, warmup=100, kernel="dense",
             stats=s_dense)
    sat8, _ = NS.saturation_point(tab8, step=0.02, cycles=1500,
                                  warmup=500, stats=s_csr)
    n512 = {
        "pod": list(GUARD_SPEC),
        "sweep_s": round(t_sweep8, 4),
        "saturation_uniform": round(sat8, 5),
        "csr_array_bytes": int(s_csr["array_bytes"]),
        "dense_array_bytes": int(s_dense["array_bytes"]),
        "bytes_ratio": round(s_dense["array_bytes"]
                             / max(s_csr["array_bytes"], 1), 2),
        "peak_rss_mb": peak_rss_mb(),
        # livelock-watchdog outputs of the guarded sweep: the cycle each
        # rate lane's watchdog fired (-1 = quiet) and how many cycles
        # the kernel actually ran (< cycles means every lane wedged and
        # the sweep ended early)
        "watchdog": {
            "cycles_run": int(s_csr.get("cycles_run", 0)),
            "stalled_at": [int(r["stalled_at"]) for r in trace8],
        },
    }
    result["n512"] = n512
    print(f"  n512: sweep({len(rates8)} rates)={t_sweep8:.2f}s "
          f"sat={sat8:.4f} csr_bytes={n512['csr_array_bytes']:,} "
          f"dense_bytes={n512['dense_array_bytes']:,} "
          f"({n512['bytes_ratio']}x) rss={n512['peak_rss_mb']}MB")
    print(f"  n512 watchdog: cycles_run="
          f"{n512['watchdog']['cycles_run']} stalled_at="
          f"{n512['watchdog']['stalled_at']}")
    emit("bench_netsim_n512_watchdog", 0,
         f"cycles_run={n512['watchdog']['cycles_run']} "
         f"stalled_at={n512['watchdog']['stalled_at']}")
    emit("bench_netsim_n512_sweep", t_sweep8 * 1e6,
         f"csr_bytes={n512['csr_array_bytes']}")
    if json_path:
        prior512 = prior.get("n512", {})
        guard_regression("netsim_n512_sweep_s", n512["sweep_s"],
                         prior512.get("sweep_s"), SWEEP_REGRESSION)
        guard_regression("netsim_n512_csr_array_bytes",
                         n512["csr_array_bytes"],
                         prior512.get("csr_array_bytes"),
                         BYTES_REGRESSION)
        # the adaptive features ride the same kernel behind python-static
        # flags: with adaptive off the staged trace is unchanged, so the
        # wall-clock must stay within 1.10x of the pre-adaptive baseline
        # (tighter than the general 1.5x sweep guard)
        guard_regression("netsim_n512_adaptive_off_overhead",
                         n512["sweep_s"], prior512.get("sweep_s"),
                         ADAPTIVE_OFF_REGRESSION)

    # ---- adaptive-routing lane (8^3, hotspot) ------------------------
    from repro.core.pipeline import PipelineConfig, route_pod

    atab8 = route_pod(topo8, PipelineConfig(
        n_vc=4, priority="robust", K=4, local_search_rounds=1,
        engine="sharded", reserve_escape=True)).tables
    spec8 = NS.adaptive_spec(topo8)
    # 8 hot endpoints at frac 0.4: consumption-limited sat ~= 0.039, so
    # a 0.005 step resolves the static-vs-adaptive gap (one hot node
    # saturates below any usable grid at n=512)
    hot8 = TrafficPattern.hotspot(topo8.n, list(range(8)), 0.4)
    t0 = time.time()
    sat_s8, tr_s8 = NS.saturation_point(atab8, step=0.005, max_rate=0.08,
                                        cycles=1500, warmup=500,
                                        traffic=hot8)
    t_stat8 = time.time() - t0
    t0 = time.time()
    sat_a8, tr_a8 = NS.saturation_point(atab8, step=0.005, max_rate=0.08,
                                        cycles=1500, warmup=500,
                                        traffic=hot8, adaptive=spec8)
    t_adapt8 = time.time() - t0
    n512["adaptive"] = {
        "hotspot_sat_static": round(sat_s8, 5),
        "hotspot_sat_adaptive": round(sat_a8, 5),
        "sat_static_s": round(t_stat8, 4),
        "sat_adaptive_s": round(t_adapt8, 4),
        # lanes whose livelock watchdog fired during the hotspot probes
        "stalled_lanes_static": sum(1 for r in tr_s8
                                    if r["stalled_at"] >= 0),
        "stalled_lanes_adaptive": sum(1 for r in tr_a8
                                      if r["stalled_at"] >= 0),
    }
    print(f"  n512 adaptive: hotspot sat static={sat_s8:.4f} "
          f"adaptive={sat_a8:.4f} ({t_stat8:.1f}s/{t_adapt8:.1f}s)")
    emit("bench_netsim_n512_adaptive_hotspot_sat", 0,
         f"static={sat_s8:.4f} adaptive={sat_a8:.4f}")
    if json_path:
        # within-run quality guard: adaptive saturation collapsing below
        # static under hotspot means the escape/overflow policy broke
        guard_regression("netsim_n512_adaptive_hotspot_sat", sat_a8,
                         sat_s8, 1.0, larger_is_worse=False)

    # ---- 12^3 saturation entry (--full; record kept across runs) -----
    if full:
        topo12 = T.pt(FULL_SPEC)
        s12: dict = {}
        t0 = time.time()
        tab12 = route_pod(topo12, PipelineConfig(
            K=4, local_search_rounds=1, engine="sharded")).tables
        t_route12 = time.time() - t0
        t0 = time.time()
        sat12, trace12 = NS.saturation_point(
            tab12, step=0.05, max_rate=0.5, cycles=1200, warmup=400,
            stats=s12)
        t_sat12 = time.time() - t0
        assert all(r["injected_total"] == r["consumed_total"]
                   + r["in_flight"] for r in trace12)
        result["n1728"] = {
            "pod": list(FULL_SPEC),
            "route_s": round(t_route12, 3),
            "sat_sweep_s": round(t_sat12, 3),
            "saturation_uniform": round(sat12, 5),
            "l_max": float(sel12.l_max),
            "csr_array_bytes": int(s12["array_bytes"]),
            "kernel": s12["kernel"],
            "peak_rss_mb": peak_rss_mb(),
        }
        print(f"  n1728: route={t_route12:.1f}s sat_sweep={t_sat12:.1f}s "
              f"sat={sat12:.4f} csr_bytes={s12['array_bytes']:,} "
              f"rss={result['n1728']['peak_rss_mb']}MB")
        emit("bench_netsim_n1728_sat", t_sat12 * 1e6, f"{sat12:.4f}")
    elif prior.get("n1728"):
        # keep the --full record around on quick runs (baseline may be
        # missing on a fresh checkout -- guards and readers tolerate it)
        result["n1728"] = prior["n1728"]

    if json_path:
        if prior.get("sweep_speedup_vs_seed"):
            print(f"  prior sweep speedup: "
                  f"{prior['sweep_speedup_vs_seed']}x")
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
         json_path=Path(__file__).parent.parent / "BENCH_netsim.json"
         if args.json else None)
