"""Timed unit ``build``: one cold build of a routed, simulable fabric.

A unit runs ``pipeline.route_pod`` from a fresh ``Topology`` (no cached
channels) with the configuration's routing settings to ``SimTables``,
then hands the tables to the chip with a probe: a short one-rate sweep,
the first packets the fabric forwards there (``chaos.probe_throughput``
is the program's own form of it). Without it the unit would run no
operation on the device.

Traffic parameters: ``probe`` (``rate``, ``cycles``, ``warmup``;
uniform traffic over the routed flows, 128 slots, 4 flits).
"""
from __future__ import annotations

import dataclasses
from typing import Any

from bench import deploy, harness
from bench.harness import kernel_seed


@dataclasses.dataclass
class State:
    seed: int
    config: dict
    traffic: dict
    cfg: Any


def setup(config: dict, traffic: dict, seed: int, spans) -> State:
    """Warms the probe's shapes, which follow the routed table. Routing
    is deterministic for a configuration, so the table that
    ``deploy.routed_tables`` keeps between runs has the shapes of every
    build: only a checkout's first run routes in set-up."""
    tables = deploy.routed_tables(config, deploy.topology(config), spans)
    with spans("warm"):
        probe(tables, traffic["probe"], kernel_seed(seed, -1), spans)
    return State(seed, config, traffic, deploy.pipeline_config(config))


def run(state: State, index: int, spans) -> dict:
    from repro.core.pipeline import route_pod
    topo = deploy.topology(state.config)
    with spans("route_pod"):
        rp = route_pod(topo, state.cfg)
    pr = probe(rp.tables, state.traffic["probe"],
               kernel_seed(state.seed, index), spans)
    return {"timings": dict(rp.timings), "l_max": rp.l_max,
            "table": rp.tables.csr(), "probe": pr}


def probe(tables, params: dict, key: int, spans) -> dict:
    from repro.core import netsim as NS
    stats: dict = {}
    with spans("probe"):
        lanes = NS.sweep(tables, [params["rate"]], cycles=params["cycles"],
                         warmup=params["warmup"], seed=key, stats=stats)
    return {"key": key, "lanes": lanes, "cycles_run": stats["cycles_run"]}


def probe_mismatches(config: dict, table, params: dict, out: dict) -> int:
    """Counters of the probe that differ from the reference's, plus
    lanes that do not conserve packets."""
    from bench.reference import fabric as RF, netsim_ref as RN
    lanes, cycles_run = RN.simulate(
        RF.fabric(config), RF.Table.of(table), {"pattern": "uniform"},
        [params["rate"]], out["key"], cycles=params["cycles"],
        warmup=params["warmup"], slots=128, flits=4)
    return RN.mismatches(out, lanes, cycles_run) + sum(
        r["injected_total"] != r["consumed_total"] + r["in_flight"]
        for r in out["lanes"])


KEYS = ("walk_errors", "missing_pairs", "cdg_cyclic", "lmax_gap",
        "probe_mismatches")


def judge(config: dict, traffic: dict, table, l_max: float,
          pr: dict) -> dict:
    """The reference's numbers for one routed table and its probe."""
    from bench.reference import fabric as RF
    rep = RF.table_report(RF.fabric(config), RF.Table.of(table))
    return {"walk_errors": rep["walk_errors"],
            "missing_pairs": rep["missing_pairs"],
            "cdg_cyclic": rep["cdg_cyclic"],
            "lmax_gap": abs(float(l_max) - float(rep["loads"].max())),
            "probe_mismatches": probe_mismatches(config, table,
                                                 traffic["probe"], pr)}


def check(run) -> list:
    worst: dict = {}
    for i, out in harness.sampled(run):
        got = judge(run.config, run.traffic, out["table"], out["l_max"],
                    out["probe"])
        print(f"reference build={i} {got}", flush=True)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0), v)
    # a key that no judged unit set reads 1: nothing was verified
    return [(k, worst.get(k, 1), 0) for k in KEYS]
