"""Timed unit ``recover``: repair of one OCS failure on a serving fabric.

Set-up builds the configuration's ``ServingState`` (the cold build that
keeps the distance fields a repair re-walks). A unit runs
``repair.repair_fault`` for every channel of one OCS color on that
pristine state (the repair is pure, so no heal is needed between
units), then hands the repaired table to the chip with the build
unit's probe: the first packets the repaired fabric forwards. Colors
come in passes over all colors in use, each pass in an order shuffled
from the seed, so every seed repairs the same colors.

The probe runs at one shape for every repair. A repair re-routes a few
thousand flows and changes the table's total hop count, and the sweep
kernel compiles once per hop count; so the probe's copy of the hop
arrays is padded, with entries that no flow reads, to the pristine
count plus a sixty-fourth (a single-OCS repair of ``pt-8x8x8`` adds 446
to 1,022 hops to its 1,575,258), and set-up warms that shape.

Traffic parameters: ``probe`` (``rate``, ``cycles``, ``warmup``), as
for ``build``.
"""
from __future__ import annotations

import dataclasses
import random
from typing import Any, List

import numpy as np

from bench import deploy, harness
from bench.harness import kernel_seed
from bench.units.build import probe, probe_mismatches


@dataclasses.dataclass
class State:
    seed: int
    traffic: dict
    serving: Any
    tables: Any          # SimTables of the pristine table
    hops: int            # the probe's padded hop count
    colors: List[int]
    order: List[int]


def setup(config: dict, traffic: dict, seed: int, spans) -> State:
    from repro.core import fault as F, netsim as NS
    from repro.core.repair import ServingState
    topo = deploy.topology(config)
    r = config["routing"]
    with spans("serving_build"):
        serving = ServingState.build(topo, n_vc=r["n_vc"], K=r["K"],
                                     seed=r["seed"], robust=r["robust"],
                                     priority=r["priority"])
    h = len(serving.table.chan)
    state = State(seed, traffic, serving,
                  NS.build_tables(topo, serving.table), h + h // 64,
                  F.colors_in_use(topo), [])
    with spans("warm"):
        probe(padded(state, serving.table), traffic["probe"],
              kernel_seed(seed, -1), spans)
    return state


def padded(state: State, table):
    """SimTables of ``table`` whose hop arrays have ``state.hops``
    entries; the flows' hop ranges are unchanged."""
    from repro.core.pathtable import CSRPathTable
    pad = state.hops - len(table.chan)
    if pad < 0:
        raise ValueError(f"repaired table has {len(table.chan)} hops, "
                         f"more than the probe's {state.hops}")
    t = CSRPathTable(table.n, table.n_ch, table.n_vc, table.src_indptr,
                     table.dst, table.hop_indptr,
                     np.concatenate([table.chan,
                                     np.zeros(pad, table.chan.dtype)]),
                     np.concatenate([table.vc,
                                     np.zeros(pad, table.vc.dtype)]))
    return dataclasses.replace(state.tables, table=t, _csr_cache=None,
                               _dense_cache=None)


def color_of(state: State, index: int) -> int:
    while len(state.order) <= index:
        p = len(state.order) // len(state.colors)
        state.order += random.Random(f"{state.seed}:{p}").sample(
            state.colors, len(state.colors))
    return state.order[index]


def run(state: State, index: int, spans) -> dict:
    from repro.core import fault as F
    from repro.core.repair import repair_fault
    color = color_of(state, index)
    dead = F.dead_channels_for_color(state.serving.at, color)
    with spans("repair_fault"):
        rr = repair_fault(state.serving, dead)
    st = rr.state
    pr = probe(padded(state, st.table), state.traffic["probe"],
               kernel_seed(state.seed, index), spans)
    return {"color": color, "stats": dict(rr.stats), "table": st.table,
            "loads": st.loads, "vc_counts": st.vc_counts, "l_max": rr.l_max,
            "probe": pr}


KEYS = ("dead_hops", "walk_errors", "missing_pairs", "cdg_cyclic",
        "load_gap", "vc_count_gap", "lmax_gap", "probe_mismatches")


def judge(config: dict, traffic: dict, out: dict) -> dict:
    """The reference's numbers for one repair and its probe."""
    from bench.reference import fabric as RF
    fab = RF.fabric(config)
    rep = RF.table_report(fab, RF.Table.of(out["table"]),
                          dead=RF.color_channels(fab, out["color"]))
    loads = np.asarray(out["loads"][:fab.n_ch], np.int64)
    vcs = np.asarray(out["vc_counts"], np.int64)
    return {"dead_hops": rep["dead_hops"],
            "walk_errors": rep["walk_errors"],
            "missing_pairs": rep["missing_pairs"],
            "cdg_cyclic": rep["cdg_cyclic"],
            "load_gap": int(np.abs(loads - rep["loads"]).max()),
            "vc_count_gap": int(np.abs(vcs - rep["vc_counts"]).max()),
            "lmax_gap": abs(float(out["l_max"])
                            - float(rep["loads"].max())),
            "probe_mismatches": probe_mismatches(
                config, out["table"], traffic["probe"], out["probe"])}


def check(run) -> list:
    worst: dict = {}
    for i, out in harness.sampled(run):
        got = judge(run.config, run.traffic, out)
        print(f"reference repair={i} color={out['color']} {got}",
              flush=True)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0), v)
    # a key that no judged unit set reads 1: nothing was verified
    return [(k, worst.get(k, 1), 0) for k in KEYS]
