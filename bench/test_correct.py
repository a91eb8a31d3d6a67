"""``correct`` comes out true for the program as it is, and false for
each fault a cell's timed path can have and for each control.

The harness runs on the CPU here with its look for a chip skipped, on
small fabrics: a 64-chip torus with the settings of ``pt-8x8x8``, and
the same torus routed for adaptive escape-VC sweeps.
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from bench import control, harness

ROOT = Path(__file__).resolve().parents[1]
PT = json.loads((ROOT / "bench/configs/pt-8x8x8.json").read_text())
TINY = dict(PT, name="tiny-torus", pod=[4, 4, 4])
TINY_ADAPTIVE = dict(TINY, name="tiny-torus-adaptive", routing={
    "n_vc": 4, "K": 4, "seed": 0, "robust": False, "priority": "robust",
    "reserve_escape": True, "engine": "sharded"})
SHORT = {"cycles": 200, "warmup": 50}
CELLS = {
    "pt8.uniform": (TINY, dict(harness.read_json("traffic", "uniform"),
                               **SHORT)),
    "tons128.hotspot-adaptive-fault": (TINY_ADAPTIVE, dict(
        harness.read_json("traffic", "hotspot-adaptive-fault"), **SHORT,
        fault={"color_index": 0, "cycle": 120})),
    "pt8.build": (TINY, harness.read_json("traffic", "cold-build")),
    "pt8.recover": (TINY, harness.read_json("traffic", "ocs-fault")),
}
SEED = 2 ** 33 + 12345


@pytest.fixture(autouse=True)
def isolated(monkeypatch, tmp_path):
    monkeypatch.setattr("repro.compile_cache.use_compile_cache",
                        lambda: "off")
    monkeypatch.setattr(harness, "STORE", tmp_path)
    monkeypatch.setattr(harness, "CHECKED", 1)


def correct(cell):
    config, traffic = CELLS[cell]
    result, _ = harness.run(cell, SEED, 0.0, False, chip_check=False,
                            config=config, traffic=traffic,
                            log=lambda s: None)
    return result["correct"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    assert correct(cell)


def _keep_sources(t, keep):
    """The table with only the flows of the first ``keep`` sources."""
    f = int(t.src_indptr[keep])
    h = int(t.hop_indptr[f])
    ptr = np.minimum(t.src_indptr, f)
    return dataclasses.replace(t, src_indptr=ptr, dst=t.dst[:f].copy(),
                               hop_indptr=t.hop_indptr[:f + 1].copy(),
                               chan=t.chan[:h].copy(), vc=t.vc[:h].copy())


def _altered_hop(t):
    t = t.copy()
    t.chan[len(t.chan) // 2] = (t.chan[len(t.chan) // 2] + 1) % t.n_ch
    return t


def _lanes_unchanged(lanes):
    return [{k: v if k == "rate" else -1 if k == "stalled_at" else 0
             for k, v in r.items()} for r in lanes]


def _lanes_half(lanes):
    half = lanes[:len(lanes) // 2]
    return half + half


def _lanes_altered(lanes):
    lanes = [dict(r) for r in lanes]
    lanes[-1]["delivered"] += 1.0 / 1024
    return lanes


@pytest.mark.parametrize("cell", ["pt8.uniform",
                                  "tons128.hotspot-adaptive-fault"])
@pytest.mark.parametrize("fault", [_lanes_unchanged, _lanes_half,
                                   _lanes_altered])
def test_broken_sweep_is_not_correct(monkeypatch, cell, fault):
    from repro.core import netsim
    sweep = netsim.sweep
    monkeypatch.setattr(netsim, "sweep",
                        lambda *a, **k: fault(sweep(*a, **k)))
    assert not correct(cell)


@pytest.mark.parametrize("fault", [
    lambda t: _keep_sources(t, 0),             # routing returns nothing
    lambda t: _keep_sources(t, t.n // 2),      # half the sources left out
    _altered_hop])                             # one hop altered
def test_broken_build_is_not_correct(monkeypatch, fault):
    from repro.core import pipeline
    route_pod = pipeline.route_pod

    def broken(*a, **k):
        rp = route_pod(*a, **k)
        rp.tables.table = fault(rp.tables.table)
        return rp
    monkeypatch.setattr(pipeline, "route_pod", broken)
    assert not correct("pt8.build")


@pytest.mark.parametrize("fault", [
    lambda s, rr: s.table.copy(),              # pristine table returned
    lambda s, rr: _keep_sources(rr.state.table, s.table.n // 2),
    lambda s, rr: _altered_hop(rr.state.table)])
def test_broken_repair_is_not_correct(monkeypatch, fault):
    from repro.core import repair
    repair_fault = repair.repair_fault

    def broken(state, dead, *a, **k):
        rr = repair_fault(state, dead, *a, **k)
        rr.state = dataclasses.replace(rr.state, table=fault(state, rr))
        return rr
    monkeypatch.setattr(repair, "repair_fault", broken)
    assert not correct("pt8.recover")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails_where_program_passes(cell):
    config, traffic = CELLS[cell]
    rows = control.readings(cell, [3, 2 ** 32 + 5, 77], config=config,
                            traffic=traffic)
    for r in rows:
        program = {k: v for k, v in r.items()
                   if k not in ("seed", "color")
                   and not k.startswith("control_")}
        assert all(v == 0 for v in program.values()), r
        assert any(v > 0 for k, v in r.items() if k.startswith("control_")), r
