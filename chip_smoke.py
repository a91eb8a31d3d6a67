#!/usr/bin/env python3
"""Smoke run of the route -> simulate path and the device LP on one TPU.

Run from the checkout root:  python chip_smoke.py

It drives the library's own entry points (``route_pod``,
``netsim.sweep``, ``netsim.saturation_point``, ``lp.solve_pdhg``) in
four phases, each printing its numbers on lines of its own:

1. device     -- the default JAX device must be a TPU; anything else
                 exits non-zero before any result is printed.
2. agreement  -- the synthesized 128-chip TONS fabric and the PT 4x4x8
                 torus are routed and swept at three rates on the TPU and
                 again on this process's host CPU backend: uniform,
                 hotspot, and adaptive hotspot with a mid-sweep OCS
                 fault. Every counter must be identical and every lane
                 must conserve packets exactly.
3. real size  -- the PT 12^3 pod (1728 chips) is routed and its
                 saturation point found twice, cold (compiling) and warm.
4. device LP  -- three seeded random LPs solved by PDHG on the chip must
                 match HiGHS on the host.

Any failed check raises, so the script exits non-zero. Its last line on
stdout is one JSON object naming the device. It reads only committed
files, makes everything else from seeds, and writes nothing into the
checkout but JAX's compile cache (see ``repro.compile_cache``).
"""
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

# the host reference runs on JAX's CPU backend in this same process, so
# keep it available when the platform list is pinned to the accelerator
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

RATES = [0.05, 0.2, 0.4]
CYCLES, WARMUP, FAULT_CYCLE = 1500, 500, 800
MIB = 1024 * 1024


def check(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def check_conserved(trace, what):
    for r in trace:
        check(r["injected_total"] == r["consumed_total"] + r["in_flight"],
              f"{what}: packets not conserved at rate {r['rate']}: {r}")


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit
    reports its load time) and counts persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.seconds, self.cache_hits = 0.0, 0


def phase_device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's default device is "
                 f"{dev.platform} ({dev.device_kind}); no result")
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    return dev


def phase_agreement():
    import jax
    from benchmarks.common import load_tons
    from repro.core import fault as F, netsim as NS, topology as T
    from repro.core.pipeline import PipelineConfig, route_pod
    from repro.core.traffic import TrafficPattern

    cpu = jax.devices("cpu")[0]
    tons = load_tons(128)
    check(tons is not None, "benchmarks/results/tons_128.pkl is missing")
    for name, topo in (("tons128", tons[0]), ("pt4x4x8", T.pt((4, 4, 8)))):
        static = route_pod(topo, PipelineConfig(K=4, engine="sharded"))
        escape = route_pod(topo, PipelineConfig(
            K=4, engine="sharded", n_vc=4, priority="robust",
            reserve_escape=True))
        ev = F.fault_event(escape.at, F.colors_in_use(topo)[0],
                           FAULT_CYCLE)
        hot = TrafficPattern.hotspot(topo.n, frac=0.4)
        cases = {
            "uniform": (static.tables, {}),
            "hotspot": (static.tables, {"traffic": hot}),
            "adaptive_hotspot_fault": (escape.tables, {
                "traffic": hot, "fault": ev,
                "adaptive": NS.adaptive_spec(topo, dead_channels=ev[1])}),
        }
        for case, (tab, kw) in cases.items():
            tpu = NS.sweep(tab, RATES, cycles=CYCLES, warmup=WARMUP, **kw)
            with jax.default_device(cpu):
                ref = NS.sweep(tab, RATES, cycles=CYCLES, warmup=WARMUP,
                               **kw)
            what = f"{name}/{case}"
            check_conserved(tpu, f"{what} on the TPU")
            check_conserved(ref, f"{what} on the CPU")
            for a, b in zip(tpu, ref):
                print(f"agreement {what} rate={a['rate']} "
                      f"delivered={a['delivered']} "
                      f"injected={a['injected_total']} "
                      f"consumed={a['consumed_total']} "
                      f"in_flight={a['in_flight']} escaped={a['escaped']} "
                      f"stalled_at={a['stalled_at']} identical={a == b}",
                      flush=True)
                check(a == b, f"{what}: TPU and CPU counters differ at "
                              f"rate {a['rate']}:\n  tpu {a}\n  cpu {b}")


def phase_real_size(dev, clock):
    from repro.core import netsim as NS, topology as T
    from repro.core.pipeline import PipelineConfig, route_pod

    t0 = time.perf_counter()
    rp = route_pod(T.pt((12, 12, 12)), PipelineConfig(K=4,
                                                      engine="sharded"))
    route_s = time.perf_counter() - t0
    check(rp.unreachable == 0, f"12^3 routing left {rp.unreachable} "
                               f"pairs unreachable")
    print(f"real_size pt12x12x12 n={rp.topo.n} route_s={route_s} "
          f"l_max={rp.l_max}", flush=True)
    traces = []
    for run in ("cold", "warm"):
        stats: dict = {}
        clock.reset()
        t0 = time.perf_counter()
        # saturation_point returns host floats, so the clock stops only
        # after the device results are back on the host
        sat, trace = NS.saturation_point(rp.tables, step=0.05,
                                         max_rate=0.5, cycles=1200,
                                         warmup=400, stats=stats)
        wall_s = time.perf_counter() - t0
        check(sat > 0, f"12^3 {run} saturation is {sat}")
        check_conserved(trace, f"12^3 {run} sweep")
        check(stats["array_bytes"] < 400 * MIB,
              f"12^3 staged {stats['array_bytes']} bytes")
        print(f"real_size saturation_point {run} wall_s={wall_s} "
              f"compile_s={clock.seconds} "
              f"persistent_cache_hits={clock.cache_hits} sat={sat} "
              f"rates={[r['rate'] for r in trace]} "
              f"array_bytes={stats['array_bytes']}", flush=True)
        traces.append(trace)
    check(traces[0] == traces[1], "12^3 cold and warm sweeps differ")
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"real_size peak_bytes_in_use={peak}", flush=True)


def phase_device_lp():
    import numpy as np
    from repro.core.lp import COOMatrix, solve_highs, solve_pdhg

    rng = np.random.default_rng(0)
    for trial in range(3):
        m, n = 30, 20
        A_d = rng.normal(size=(m, n))
        rows, cols = np.nonzero(np.abs(A_d) > 0.7)
        A = COOMatrix.from_triplets(rows, cols, A_d[rows, cols], (m, n))
        c = rng.normal(size=n)
        x_feas = rng.uniform(0, 1, n)
        b = A.to_scipy() @ x_feas + rng.uniform(0.1, 1.0, m)
        lo, hi = np.zeros(n), np.ones(n)
        host = solve_highs(c, A, b, lo, hi)
        dev = solve_pdhg(c, A, b, lo, hi, max_iters=20000, tol=1e-6)
        print(f"device_lp trial={trial} pdhg_obj={dev.obj} "
              f"highs_obj={host.obj} status={dev.status} "
              f"iters={dev.iters} rel_gap={dev.rel_gap} "
              f"primal_infeas={dev.primal_infeas}", flush=True)
        check(abs(host.obj - dev.obj) < 1e-3 * (1 + abs(host.obj)),
              f"LP {trial}: PDHG {dev.obj} vs HiGHS {host.obj}")


def main():
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    dev = phase_device()
    clock = CompileClock()
    print(f"compile_cache dir={cache_dir}", flush=True)
    phase_agreement()
    phase_real_size(dev, clock)
    phase_device_lp()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
