"""Timed unit ``sweep``: one load curve of a routed fabric.

A unit compiles the traffic mix onto the routed table's flow slots
(``traffic.compile_flow_traffic``) and simulates every rate of the mix
in one ``netsim.sweep``, whose results are on the host when it returns.
Unit ``i`` draws its kernel key from the run's seed and ``i``.

Traffic parameters: ``pattern``, a file ``bench/patterns/<pattern>.py``,
with that pattern's own parameters; ``rates``, ``cycles``, ``warmup``,
``slots``, ``flits``, ``routing`` (``static`` or ``adaptive``), and
optionally ``fault`` (``color_index`` into the sorted OCS colors in use,
and ``cycle``).
"""
from __future__ import annotations

import dataclasses
import types
from typing import Any

import numpy as np

from bench import deploy, harness
from bench.harness import kernel_seed


@dataclasses.dataclass
class State:
    seed: int
    traffic: dict
    tables: Any
    pattern: Any
    kw: dict


def setup(config: dict, traffic: dict, seed: int, spans) -> State:
    from repro.core import fault as F, netsim as NS
    from repro.core.routing import Channels
    topo = deploy.topology(config)
    tables = deploy.routed_tables(config, topo, spans)
    kw: dict = {}
    dead = None
    if "fault" in traffic:
        color = F.colors_in_use(topo)[traffic["fault"]["color_index"]]
        # fault_event reads only the channels of the admission result
        at = types.SimpleNamespace(channels=Channels.from_topology(topo))
        kw["fault"] = F.fault_event(at, color, traffic["fault"]["cycle"])
        dead = kw["fault"][1]
    if traffic["routing"] == "adaptive":
        with spans("adaptive_spec"):
            kw["adaptive"] = NS.adaptive_spec(topo, dead_channels=dead)
    pattern = harness.load_module("patterns", traffic["pattern"]) \
        .program(traffic, topo.n)
    state = State(seed, traffic, tables, pattern, kw)
    with spans("warm"):
        run(state, -1, spans)
    return state


def run(state: State, index: int, spans) -> dict:
    from repro.core import netsim as NS
    from repro.core.traffic import compile_flow_traffic
    tr = state.traffic
    csr = state.tables.csr()
    key = kernel_seed(state.seed, index)
    with spans("traffic"):
        ct = compile_flow_traffic(state.pattern, csr.src_indptr, csr.dst)
    stats: dict = {}
    with spans("sweep"):
        lanes = NS.sweep(state.tables, tr["rates"], ct, cycles=tr["cycles"],
                         warmup=tr["warmup"], slots=tr["slots"], seed=key,
                         flits=tr["flits"], stats=stats, **state.kw)
    return {"key": key, "lanes": lanes, "cycles_run": stats["cycles_run"],
            "array_bytes": stats["array_bytes"]}


def reference(config: dict, state: State, key: int, dtype: str = "float32"):
    """The reference's lanes and cycle count for kernel key ``key``."""
    from bench.reference import fabric as RF, netsim_ref as RN
    tr = state.traffic
    fab = RF.fabric(config)
    fault = None
    if "fault" in tr:
        colors = np.unique(fab.color[fab.color >= 0])
        dead = RF.color_channels(fab, int(colors[tr["fault"]["color_index"]]))
        fault = (tr["fault"]["cycle"], dead)
    return RN.simulate(fab, RF.Table.of(state.tables.csr()), tr,
                       tr["rates"], key, cycles=tr["cycles"],
                       warmup=tr["warmup"], slots=tr["slots"],
                       flits=tr["flits"], adaptive=tr["routing"] == "adaptive",
                       fault=fault, dtype=dtype)


def check(run) -> list:
    from bench.reference import fabric as RF, netsim_ref as RN
    from repro.core.routing import Channels
    state: State = run.state
    fab = RF.fabric(run.config)
    ch = Channels.from_topology(deploy.topology(run.config))
    chan_bad = sum(not np.array_equal(a, b) for a, b in
                   ((ch.src, fab.src), (ch.dst, fab.dst),
                    (ch.color, fab.color)))
    rep = RF.table_report(fab, RF.Table.of(state.tables.csr()))
    done = [o for o in run.outputs if o is not None]
    unconserved = sum(r["injected_total"] != r["consumed_total"]
                      + r["in_flight"] for o in done for r in o["lanes"])
    bad = 0
    for i, out in harness.sampled(run):
        lanes, cycles_run = reference(run.config, state, out["key"])
        bad += RN.mismatches(out, lanes, cycles_run)
        print(f"reference sweep={i} key={out['key']} "
              f"lanes={out['lanes']} cycles_run={out['cycles_run']} "
              f"array_bytes={out['array_bytes']}", flush=True)
    return [("counter_mismatches", bad, 0),
            ("unconserved_lanes", unconserved, 0),
            ("table_errors", rep["walk_errors"] + rep["missing_pairs"], 0),
            ("channel_mismatches", chan_bad, 0)]
