"""Timed unit ``replay``: one expert-parallel decode step replayed on a
routed fabric.

A unit routes a fresh decode step (``workload.ep_decode_trace``, seeded
from the run's seed and the unit's index), compiles its phased demand
onto the routed table's flow slots (``traffic.compile_flow_traffic``)
and simulates every rate of the mix in one ``netsim.sweep``, whose
results are on the host when it returns. Every unit has the same shapes
(the phases, the period and the table's flows), so the window compiles
nothing.

Traffic parameters: ``arch`` (a router of ``workload.ROUTERS``) and its
``published`` settings, ``tokens_per_chip``, ``layers``,
``experts_per_chip``, ``period``, ``bytes_per_copy``, ``rates``,
``cycles``, ``warmup``, ``slots``, ``flits`` and ``routing`` (``static``
or ``adaptive``).

The check routes the sampled units' steps again with the plain router
(``bench/reference/ep_route.py``) and replays them with the plain phased
simulator (``bench/reference/phased_ref.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from bench import deploy, harness
from bench.harness import kernel_seed


@dataclasses.dataclass
class State:
    seed: int
    config: dict
    traffic: dict
    tables: Any
    kw: dict


def setup(config: dict, traffic: dict, seed: int, spans) -> State:
    from repro.core import netsim as NS
    topo = deploy.topology(config)
    tables = deploy.routed_tables(config, topo, spans)
    kw: dict = {}
    if traffic["routing"] == "adaptive":
        with spans("adaptive_spec"):
            kw["adaptive"] = NS.adaptive_spec(topo)
    state = State(seed, config, traffic, tables, kw)
    with spans("warm"):
        run(state, -1, spans)
    return state


def step(config: dict, traffic: dict, key: int):
    """The program's phased demand of the decode step drawn with ``key``."""
    from repro.core import workload
    return workload.ep_decode_trace(
        tuple(config["pod"]), traffic["arch"],
        tokens_per_chip=traffic["tokens_per_chip"], layers=traffic["layers"],
        period=traffic["period"], seed=key,
        experts_per_chip=traffic["experts_per_chip"])


def run(state: State, index: int, spans) -> dict:
    from repro.core import netsim as NS
    from repro.core.traffic import compile_flow_traffic
    tr = state.traffic
    csr = state.tables.csr()
    key = kernel_seed(state.seed, index)
    with spans("ep_trace"):
        trace = step(state.config, tr, key)
    with spans("traffic"):
        ct = compile_flow_traffic(trace, csr.src_indptr, csr.dst)
    stats: dict = {}
    with spans("sweep"):
        lanes = NS.sweep(state.tables, tr["rates"], ct, cycles=tr["cycles"],
                         warmup=tr["warmup"], slots=tr["slots"], seed=key,
                         flits=tr["flits"], stats=stats, **state.kw)
    return {"key": key, "lanes": lanes, "cycles_run": stats["cycles_run"],
            "array_bytes": stats["array_bytes"],
            "phases": [(p.matrix, p.src_rate) for p in trace.patterns],
            "phase_cycles": list(trace.cycles)}


def reference_demand(config: dict, traffic: dict, key: int,
                     dtype: str = "float64"):
    """The plain router's routes, phases and phase cycles for ``key``."""
    from bench.reference import ep_route as ER
    n = int(np.prod(config["pod"]))
    routes = ER.route(traffic, n * traffic["tokens_per_chip"],
                      traffic["layers"], key, dtype=dtype)
    phases, cycles = ER.phases(traffic, ER.copies(traffic, routes, n), n)
    return routes, phases, cycles


def reference(config: dict, state: State, phases, cycles, key: int,
              dtype: str = "float32"):
    """The plain phased simulator's lanes and cycle count."""
    from bench.reference import fabric as RF, phased_ref as RP
    tr = state.traffic
    return RP.simulate(RF.fabric(config), RF.Table.of(state.tables.csr()),
                       phases, cycles, tr["rates"], key,
                       cycles=tr["cycles"], warmup=tr["warmup"],
                       slots=tr["slots"], flits=tr["flits"],
                       adaptive=tr["routing"] == "adaptive", dtype=dtype)


def route_mismatches(config: dict, traffic: dict, key: int,
                     routes: list) -> tuple:
    """Tokens whose expert set the program routes otherwise than
    ``routes``, and tokens the program sends to more than ``topk_group``
    expert groups."""
    from repro.core import workload
    spec = workload.ROUTERS[traffic["arch"]]
    n = int(np.prod(config["pod"]))
    got = workload.route_tokens(spec, n * traffic["tokens_per_chip"],
                                traffic["layers"], key)
    want = np.asarray(routes, np.int64)
    bad = int((np.sort(got, axis=2) != want).any(axis=2).sum()) \
        if got.shape == want.shape else got.shape[0] * got.shape[1]
    width = spec.n_routed_experts // spec.n_group
    groups = np.sort(got // width, axis=2)
    reached = 1 + (groups[:, :, 1:] != groups[:, :, :-1]).sum(axis=2)
    return bad, int((reached > traffic["published"]["topk_group"]).sum())


def demand_mismatches(out: dict, phases, cycles) -> int:
    """Phase matrices, rate vectors and cycle counts of a unit's output
    that are not bit-equal to the reference's."""
    bad = int(list(out["phase_cycles"]) != list(cycles)) \
        + abs(len(out["phases"]) - len(phases))
    for (m, r), (m_ref, r_ref) in zip(out["phases"], phases):
        bad += int(not np.array_equal(m, m_ref))
        bad += int(r.dtype != np.float32
                   or not np.array_equal(r, r_ref))
    return bad


def check(run) -> list:
    from bench.reference import netsim_ref as RN
    state: State = run.state
    done = [o for o in run.outputs if o is not None]
    unconserved = sum(r["injected_total"] != r["consumed_total"]
                      + r["in_flight"] for o in done for r in o["lanes"])
    routes_bad = demand_bad = groups_bad = bad = 0
    for i, out in harness.sampled(run):
        routes, phases, cycles = reference_demand(run.config, run.traffic,
                                                  out["key"])
        r, g = route_mismatches(run.config, run.traffic, out["key"], routes)
        routes_bad, groups_bad = routes_bad + r, groups_bad + g
        demand_bad += demand_mismatches(out, phases, cycles)
        lanes, cycles_run = reference(run.config, state, phases, cycles,
                                      out["key"])
        bad += RN.mismatches(out, lanes, cycles_run)
        print(f"reference replay={i} key={out['key']} "
              f"phase_cycles={out['phase_cycles']} lanes={out['lanes']} "
              f"cycles_run={out['cycles_run']} "
              f"array_bytes={out['array_bytes']}", flush=True)
    return [("route_mismatches", routes_bad, 0),
            ("demand_mismatches", demand_bad, 0),
            ("counter_mismatches", bad, 0),
            ("unconserved_lanes", unconserved, 0),
            ("group_violations", groups_bad, 0)]


def control_readings(config: dict, traffic: dict, seeds, spans) -> list:
    """The controls of the checks behind ``correct``, as
    ``bench/control.py`` gives them for the other units. Per seed: each
    mismatch count of the program's unit against the reference as the
    traffic states it (router float64, injection float32), and against
    the controls one precision lower (router float32, injection
    bfloat16)."""
    from bench.reference import netsim_ref as RN
    state = setup(config, traffic, seeds[0], spans)
    rows = []
    for s in seeds:
        state.seed = s
        got = run(state, 0, spans)
        key = got["key"]
        routes, phases, cycles = reference_demand(config, traffic, key)
        ctl_routes, ctl_phases, ctl_cycles = reference_demand(
            config, traffic, key, dtype="float32")
        ref = reference(config, state, phases, cycles, key)
        ctl = reference(config, state, phases, cycles, key,
                        dtype="bfloat16")
        rows.append({
            "seed": s,
            "route_mismatches": route_mismatches(config, traffic, key,
                                                 routes)[0],
            "demand_mismatches": demand_mismatches(got, phases, cycles),
            "counter_mismatches": RN.mismatches(got, *ref),
            "control_route_mismatches": route_mismatches(
                config, traffic, key, ctl_routes)[0],
            "control_demand_mismatches": demand_mismatches(
                got, ctl_phases, ctl_cycles),
            "control_counter_mismatches": RN.mismatches(got, *ctl)})
    return rows
