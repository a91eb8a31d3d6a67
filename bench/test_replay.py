"""The simulator's phased path against the plain phased reference, and
the ``replay`` unit's check, on the 4x4x8 TONS fabric at short lengths."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import deploy, harness
from bench.reference import fabric as RF, netsim_ref as RN, phased_ref as RP
from bench.units import replay

ROOT = Path(__file__).resolve().parents[1]
TONS = json.loads((ROOT / "bench/configs/tons-128.json").read_text())
SHORT = {"tokens_per_chip": 4, "period": 96, "cycles": 240, "warmup": 60}
SEED = 2 ** 33 + 4321


@pytest.fixture(scope="module")
def routed(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "STORE", tmp_path_factory.mktemp("store"))
    topo = deploy.topology(TONS)
    tables = deploy.routed_tables(TONS, topo, harness.Spans())
    yield topo, tables
    mp.undo()


def traffic(**kw):
    return dict(harness.read_json("traffic", "dsv3-ep-decode"), **SHORT,
                **kw)


@pytest.mark.parametrize("routing", ["static", "adaptive"])
def test_phased_sweep_matches_reference(routed, routing):
    from repro.core import netsim as NS
    topo, tables = routed
    tr = traffic(routing=routing)
    kw = {"adaptive": NS.adaptive_spec(topo)} if routing == "adaptive" \
        else {}
    trace = replay.step(TONS, tr, SEED)
    assert len(trace.patterns) == 8
    stats: dict = {}
    lanes = NS.sweep(tables, tr["rates"], trace, cycles=tr["cycles"],
                     warmup=tr["warmup"], slots=tr["slots"], seed=SEED,
                     flits=tr["flits"], stats=stats, **kw)
    _, phases, cycles = replay.reference_demand(TONS, tr, SEED)
    want, cycles_run = RP.simulate(
        RF.fabric(TONS), RF.Table.of(tables.csr()), phases, cycles,
        tr["rates"], SEED, cycles=tr["cycles"], warmup=tr["warmup"],
        slots=tr["slots"], flits=tr["flits"], adaptive=routing == "adaptive")
    out = {"lanes": lanes, "cycles_run": stats["cycles_run"]}
    assert RN.mismatches(out, want, cycles_run) == 0
    assert all(r["injected_total"] > 0 for r in lanes)


def test_one_phase_replay_is_its_stationary_pattern(routed):
    from repro.core import netsim as NS
    from repro.core.traffic import PhasedTraffic
    _, tables = routed
    pattern = replay.step(TONS, traffic(), SEED).patterns[1]   # a combine
    kw = dict(cycles=200, warmup=50, slots=128, seed=SEED, flits=4)
    a = NS.sweep(tables, [0.05, 0.2], pattern, **kw)
    b = NS.sweep(tables, [0.05, 0.2],
                 PhasedTraffic("one", (pattern,), (37,)), **kw)
    assert a == b
    assert a[0]["injected_total"] > 0


def test_replay_check_is_all_zeros(routed, monkeypatch, tmp_path):
    monkeypatch.setattr("repro.compile_cache.use_compile_cache",
                        lambda: "off")
    monkeypatch.setattr(harness, "STORE", tmp_path)
    monkeypatch.setattr(harness, "CHECKED", 1)
    result, checks = harness.run("tons128.dsv3-ep-decode", SEED, 0.0, False,
                                 chip_check=False, config=TONS,
                                 traffic=traffic(), log=lambda s: None)
    assert [k for k, _, _ in checks] == [
        "route_mismatches", "demand_mismatches", "counter_mismatches",
        "unconserved_lanes", "group_violations"]
    assert all(v == 0 for _, v, _ in checks), checks
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"]["sweep_s"]["value"] > 0


def test_control_fails_where_program_passes(routed):
    rows = replay.control_readings(TONS, traffic(), [3, 2 ** 32 + 5],
                                   harness.Spans())
    for r in rows:
        assert r["route_mismatches"] == r["demand_mismatches"] \
            == r["counter_mismatches"] == 0, r
        assert r["control_counter_mismatches"] > 0, r


def test_demand_mismatch_counts_each_differing_piece():
    m = np.arange(16.0).reshape(4, 4)
    r = np.ones(4, np.float32)
    out = {"phases": [(m, r), (m.T, r)], "phase_cycles": [3, 5]}
    assert replay.demand_mismatches(out, [(m, r), (m.T, r)], [3, 5]) == 0
    assert replay.demand_mismatches(out, [(m, r), (m, r)], [3, 5]) == 1
    assert replay.demand_mismatches(out, [(m, r * 2), (m.T, r)], [4, 4]) == 2
    assert replay.demand_mismatches(out, [(m, r)], [3, 5]) == 1
    out["phases"][0] = (m, r.astype(np.float64))
    assert replay.demand_mismatches(out, [(m, r), (m.T, r)], [3, 5]) == 1
