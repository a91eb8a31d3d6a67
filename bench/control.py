#!/usr/bin/env python3
"""The controls of the checks behind ``correct``, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

For every seed it prints, for the program's output, each number the
cell's check compares next to the same number with the control in the
program's place: the reference computed with bfloat16 injection
arithmetic for a sweep (the configurations state float32), and for a
build or a repair the same routed table with its VC allocation dropped
(every hop on VC 0), which breaks the deadlock freedom the
configurations state. A sound control reads above the limit; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
from pathlib import Path

import numpy as np


def _no_vcs(table):
    return dataclasses.replace(table, vc=np.zeros_like(table.vc))


def sweep_readings(config: dict, traffic: dict, seeds, spans):
    """Per seed: counter mismatches of the program's sweep against the
    float32 reference (the limit's lower reading) and against the
    bfloat16 control (its upper reading)."""
    from bench.reference import netsim_ref as RN
    from bench.units import sweep
    state = sweep.setup(config, traffic, seeds[0], spans)
    out = []
    for s in seeds:
        state.seed = s
        got = sweep.run(state, 0, spans)
        ref = sweep.reference(config, state, got["key"])
        ctl = sweep.reference(config, state, got["key"], dtype="bfloat16")
        out.append({"seed": s,
                    "counter_mismatches": RN.mismatches(got, *ref),
                    "control_counter_mismatches": RN.mismatches(got, *ctl)})
    return out


def build_readings(config: dict, traffic: dict, spans):
    """The build is deterministic (routing seed 0), so one build serves
    every seed: the program's numbers and the VC-dropped control's."""
    from bench.reference import fabric as RF
    from bench.units import build
    state = build.setup(config, traffic, 0, spans)
    out = build.run(state, 0, spans)
    got = build.judge(config, traffic, out["table"], out["l_max"],
                      out["probe"])
    ctl = RF.table_report(RF.fabric(config),
                          _no_vcs(RF.Table.of(out["table"])))
    return [dict(got, control_cdg_cyclic=ctl["cdg_cyclic"])]


def recover_readings(config: dict, traffic: dict, seeds, spans):
    """Per seed: the first repair of the seed's color order, judged as
    the program made it and with its VC allocation dropped."""
    from bench.reference import fabric as RF
    from bench.units import recover
    state = recover.setup(config, traffic, seeds[0], spans)
    fab = RF.fabric(config)
    out = []
    for s in seeds:
        state.seed, state.order = s, []
        got = recover.run(state, 0, spans)
        ctl = RF.table_report(fab, _no_vcs(RF.Table.of(got["table"])),
                              dead=RF.color_channels(fab, got["color"]))
        out.append(dict(recover.judge(config, traffic, got), seed=s,
                        color=got["color"],
                        control_cdg_cyclic=ctl["cdg_cyclic"]))
    return out


def readings(cell: str, seeds, config=None, traffic=None):
    from bench import harness
    _, config, traffic = harness.cell_inputs(harness.load_benchmark(), cell,
                                             config, traffic)
    spans = harness.Spans()
    kind = traffic["unit"]
    if kind == "sweep":
        return sweep_readings(config, traffic, seeds, spans)
    if kind == "build":
        return build_readings(config, traffic, spans)
    if kind == "recover":
        return recover_readings(config, traffic, seeds, spans)
    raise ValueError(f"no control for unit {kind!r}")


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    a = p.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    for r in readings(a.workload, [int(s) for s in a.seeds.split(",")]):
        print(json.dumps({"workload": a.workload, **r}), flush=True)
    return 0


if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.exit(main())
