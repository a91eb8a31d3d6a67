"""The per-layer readers of the program's own spans and counters:
numbers from a synthetic run, None where the window holds none of them
or the program keeps no record, and numbers from a traced run of each
cell on the CPU at a small size."""
import json
import sys
from pathlib import Path

import pytest

from bench import harness
from repro.core import obs

ROOT = Path(__file__).resolve().parents[1]
READERS = ("host_ms.sweep", "hop_ns.sweep", "bfs_s.build", "walk_s.build",
           "repair_ms.recover")
LO, HI = 100.0, 200.0      # the window on the perf_counter clock


def read(name, run):
    return harness.load_module("layers", name).read(run)


def _run(trace=None):
    spans = harness.Spans()
    spans.records.append(("window", LO, HI - LO))
    return harness.Run(seed=1, config={}, traffic={}, state=None,
                       outputs=[], unit_s=[1.0], window_s=HI - LO,
                       spans=spans, trace=trace)


class _Calls:
    """Spans of made-up calls, each a root with children."""

    def __init__(self, rec):
        self.rec, self.next = rec, 1

    def call(self, name, start, end, children=()):
        root = self._add(name, start, end, 0, None)
        for child, s, e in children:
            self._add(child, s, e, root.id, root.id)
        return root

    def _add(self, name, start, end, parent, root):
        s = obs.Span(name, start, self.next, parent, root or self.next)
        s.end = end
        self.next += 1
        self.rec.spans.append(s)
        return s


@pytest.fixture
def rec(monkeypatch):
    r = obs.Recorder()
    monkeypatch.setattr(obs, "RECORDER", r)
    return r


def test_host_ms_sweep_is_the_sweep_less_its_run(rec):
    c = _Calls(rec)
    c.call("netsim.sweep", 110.0, 114.0, [("netsim.sweep.run", 110.5, 113.9)])
    c.call("netsim.sweep", 120.0, 123.0, [("netsim.sweep.run", 120.2, 122.9)])
    c.call("netsim.sweep", 90.0, 95.0, [("netsim.sweep.run", 90.1, 94.0)])
    assert read("host_ms.sweep", _run()) == pytest.approx(
        1000 * (0.6 + 0.3) / 2)


def test_hop_ns_sweep_is_kernel_time_over_hops(rec):
    for t, hops in ((150.0, 1_000_000), (160.0, 3_000_000), (50.0, 7)):
        rec.counts.append(obs.Count("netsim.sweep.hops", hops, t, 0, 0))
    trace = {"programs": {"jit__sweep_csr(123)": 2.0,
                          "jit__sweep_dense(9)": 1.0,
                          "jit_other(4)": 50.0}}
    assert read("hop_ns.sweep", _run(trace)) == pytest.approx(
        1e9 * 3.0 / 4_000_000)
    assert read("hop_ns.sweep", _run()) is None


def test_build_readers_are_seconds_per_build(rec):
    c = _Calls(rec)
    for t0 in (101.0, 140.0):
        c.call("pipeline.route_pod", t0, t0 + 12.0, [
            ("routing.select.bfs", t0 + 4.0, t0 + 6.5),
            ("routing.select.walk", t0 + 6.5, t0 + 7.5),
            ("routing.select.walk", t0 + 7.6, t0 + 8.6)])
    c.call("pipeline.route_pod", 10.0, 22.0,
           [("routing.select.bfs", 11.0, 19.0)])
    run = _run()
    assert read("bfs_s.build", run) == pytest.approx(2.5)
    assert read("walk_s.build", run) == pytest.approx(2.0)


def test_repair_ms_recover_is_the_mean_repair(rec):
    c = _Calls(rec)
    c.call("repair.repair_fault", 150.0, 150.3,
           [("repair.repair_fault.walk", 150.0, 150.1)])
    c.call("repair.repair_fault", 151.0, 151.5)
    c.call("repair.repair_fault", 250.0, 259.0)
    assert read("repair_ms.recover", _run()) == pytest.approx(400.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_spans_in_the_window(rec, name):
    c = _Calls(rec)
    # the program's spans of set-up, before the window
    c.call("netsim.sweep", 10.0, 12.0, [("netsim.sweep.run", 10.1, 11.0)])
    c.call("pipeline.route_pod", 20.0, 30.0,
           [("routing.select.bfs", 21.0, 22.0),
            ("routing.select.walk", 22.0, 23.0)])
    c.call("repair.repair_fault", 40.0, 41.0)
    rec.counts.append(obs.Count("netsim.sweep.hops", 9, 11.5, 0, 0))
    assert read(name, _run({"programs": {"jit__sweep_csr(1)": 1.0}})) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_program_without_a_recorder(
        rec, monkeypatch, name):
    """A checkout whose program predates the recorder: the import fails
    and the metric is left out, without raising."""
    _Calls(rec).call("netsim.sweep", 110.0, 114.0)
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    monkeypatch.delattr("repro.core.obs")
    monkeypatch.delattr("repro.core.netsim.KERNEL_PROGRAMS")
    assert read(name, _run({"programs": {"jit__sweep_csr(1)": 1.0}})) is None


# ---------------------------------------------------------------------------
# traced runs of each cell on the CPU, at a small size
# ---------------------------------------------------------------------------

PT = json.loads((ROOT / "bench/configs/pt-8x8x8.json").read_text())
TINY = dict(PT, name="tiny-torus", pod=[4, 4, 4])
SHORT = {"cycles": 200, "warmup": 50}
CELLS = {
    "pt8.uniform": (dict(harness.read_json("traffic", "uniform"), **SHORT),
                    ["host_ms.sweep"]),
    "pt8.build": (harness.read_json("traffic", "cold-build"),
                  ["bfs_s.build", "walk_s.build"]),
    "pt8.recover": (harness.read_json("traffic", "ocs-fault"),
                    ["repair_ms.recover"]),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_cpu_run_reports_the_program_metrics(cell, monkeypatch,
                                                    tmp_path):
    monkeypatch.setattr("repro.compile_cache.use_compile_cache",
                        lambda: "off")
    monkeypatch.setattr(harness, "STORE", tmp_path / "store")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(harness, "CHECKED", 1)
    traffic, names = CELLS[cell]
    result, _ = harness.run(cell, 5, 0.0, True, chip_check=False,
                            config=TINY, traffic=traffic,
                            log=lambda s: None)
    assert result["correct"]
    for name in names:
        assert result["metrics"][name]["value"] > 0, name
