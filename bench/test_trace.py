"""The trace reduction: busy union, program time, top operations and
idle gaps named by the host span open during them."""
from pathlib import Path

import pytest

from bench import trace

RECORDED = Path(__file__).resolve().parent / "testdata" / "probe.xplane.pb"


def test_union_merges_overlaps():
    assert trace.union([(4, 5), (1, 2), (1.5, 2.5), (2.5, 3)]) == \
        [(1, 3), (4, 5)]


def test_reduce_synthetic_trace():
    tr = {"devices": {
        "/device:TPU:0": {
            "ops": [("a", 1.0, 2.0), ("b", 1.5, 2.5), ("a", 4.0, 5.0),
                    ("c", 9.5, 11.0), ("a", -2.0, -1.0)],
            "modules": [("jit__sweep_csr", 1.0, 2.5),
                        ("jit__sweep_csr", 4.0, 5.0),
                        ("jit_other", 9.5, 11.0)]},
        "/device:TPU:1": {"ops": [], "modules": []}},
        "spans": [("window", 0.0, 10.0), ("unit", 0.0, 5.5),
                  ("traffic", 0.0, 1.0), ("sweep", 1.0, 5.5),
                  ("unit", 6.0, 10.0), ("later", 20.0, 21.0)]}
    r = trace.reduce(tr)
    assert r["window_s"] == 10.0
    # busy: [1, 2.5] + [4, 5] + [9.5, 10], clipped to the window; the
    # device that ran nothing does not count in the mean
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["programs"] == pytest.approx({"jit__sweep_csr": 2.5,
                                           "jit_other": 0.5})
    assert r["device_ops"] == [["a", 2.0], ["b", 1.0], ["c", 0.5]]
    assert [[n, pytest.approx(s)] for n, s in r["idle_gaps"]] == [
        ["unit", 4.5], ["sweep", 1.5], ["traffic", 1.0]]


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": []})


def test_reduce_recorded_tpu_trace():
    """A TPU v5e trace of a window holding three executions of one
    jitted loop (``probe_kernel``) with a 20 ms ``sleep`` span after
    each, then a 26 ms host-to-device ``put``, then one more sleep."""
    tr = trace.load(RECORDED)
    assert list(tr["devices"]) == ["/device:TPU:0"]
    mods = tr["devices"]["/device:TPU:0"]["modules"]
    assert [n.startswith("jit_probe_kernel") for n, _, _ in mods] == \
        [True] * 3
    r = trace.reduce(tr)
    assert r["window_s"] == pytest.approx(0.1123, abs=1e-4)
    ((name, seconds),) = r["programs"].items()
    assert name.startswith("jit_probe_kernel")
    lo, hi = [(s, e) for n, s, e in tr["spans"] if n == "window"][0]
    inside = [(s, e) for _, s, e in mods if e > lo and s < hi]
    assert seconds == pytest.approx(sum(min(e, hi) - max(s, lo)
                                        for s, e in inside))
    # the loop's ops cover its module up to the gaps between them
    assert 0.9 * seconds < r["busy_s"] <= seconds
    assert r["device_ops"][0][0].startswith("fusion")
    assert all(not n.startswith("while") for n, _ in r["device_ops"])
    # a transfer runs no device operation: the put and the sleeps are
    # idle, each gap named by the span open at its middle
    names = [n for n, _ in r["idle_gaps"]]
    assert names[0] == "put"
    assert r["idle_gaps"][0][1] > 0.06
    assert set(names) <= {"put", "sleep", "unit"}
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"])
