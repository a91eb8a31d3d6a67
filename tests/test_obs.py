"""The program's span recorder (`repro.core.obs`) and the timing keys it
feeds: nesting and request ids, the bounded ring, selection by a
``perf_counter`` window, spans on the profiler's timeline, span names,
and every stage timing of the build, selection and repair paths equal to
the spans behind it."""
import re
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import fault as F, obs, topology as T
from repro.core.pipeline import PipelineConfig, route_pod
from repro.core.repair import ServingState, repair_fault, restore_channels

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def test_spans_nest_with_parent_and_root_ids():
    rec = obs.Recorder()
    with rec.span("a") as a:
        with rec.span("a.b") as b:
            with rec.span("a.b.c") as c:
                rec.count("a.items", 3)
        with rec.span("a.d") as d:
            pass
    with rec.span("e") as e:
        pass
    assert [s.name for s in rec.spans] == ["a.b.c", "a.b", "a.d", "a", "e"]
    assert (a.parent, b.parent, c.parent, d.parent) == (0, a.id, b.id, a.id)
    assert {a.root, b.root, c.root, d.root} == {a.id}
    assert e.parent == 0 and e.root == e.id != a.id
    assert a.start <= b.start <= c.start <= c.end <= b.end <= d.start \
        <= d.end <= a.end
    assert a.seconds == a.end - a.start > 0
    (n,) = rec.counts
    assert (n.name, n.value, n.span, n.root) == ("a.items", 3, c.id, a.id)
    assert b.start <= n.time <= b.end


def test_span_closes_and_records_when_the_block_raises():
    rec = obs.Recorder()
    with pytest.raises(KeyError):
        with rec.span("outer") as outer:
            with rec.span("outer.inner"):
                raise KeyError("x")
    assert [s.name for s in rec.spans] == ["outer.inner", "outer"]
    with rec.span("next") as nxt:
        pass
    assert nxt.parent == 0 and outer.end >= outer.start


def test_each_thread_keeps_its_own_stack():
    rec = obs.Recorder()
    seen = {}

    def work():
        with rec.span("worker") as w:
            seen["w"] = w

    with rec.span("main") as m:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    assert seen["w"].parent == 0 and seen["w"].root == seen["w"].id
    assert m.parent == 0


def test_ring_keeps_the_newest_spans_and_counts():
    rec = obs.Recorder(size=4)
    for i in range(10):
        with rec.span(f"s.{i}"):
            rec.count("c", i)
    assert [s.name for s in rec.spans] == ["s.6", "s.7", "s.8", "s.9"]
    assert [c.value for c in rec.counts] == [6, 7, 8, 9]
    assert obs.RECORDER.spans.maxlen == obs.RING == 65536


def test_between_selects_spans_that_start_in_a_perf_counter_window():
    rec = obs.Recorder()
    with rec.span("x.before"):
        rec.count("n", 1)
    lo = time.perf_counter()
    with rec.span("x.inside") as inside:
        rec.count("n", 2)
    with rec.span("x.inside"):
        pass
    hi = time.perf_counter()
    with rec.span("x.after"):
        rec.count("n", 3)
    got = rec.between(lo, hi)
    assert [s.name for s in got] == ["x.inside", "x.inside"]
    assert got[0] is inside
    assert rec.between(lo, hi, "x.before") == []
    assert [c.value for c in rec.counts_between(lo, hi, "n")] == [2]


def test_spans_appear_as_host_events_of_a_cpu_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("netsim.trace_probe") as outer:
            with obs.span("netsim.trace_probe.inner") as inner:
                jax.block_until_ready(jax.numpy.arange(8) * 2)
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(e.duration_ns * 1e-9)
    for s in (outer, inner):
        (d,) = events[s.name]
        # the recorded interval lies inside the annotation's
        assert s.seconds <= d < s.seconds + 1e-3


def _span_names():
    """Every literal name passed to ``obs.span`` in the program."""
    names = {}
    for f in SRC.rglob("*.py"):
        for name in re.findall(r'obs\.span\(\s*"([^"]+)"', f.read_text()):
            names[name] = f
    return names


def test_program_span_names_are_module_dot_stage():
    names = _span_names()
    assert {"pipeline.route_pod", "routing.select.bfs", "netsim.sweep.run",
            "repair.repair_fault.walk", "traffic.compile"} <= set(names)
    modules = {f.stem for f in (SRC / "core").glob("*.py")}
    for name, f in names.items():
        assert not name.startswith("bench."), name
        head, *stages = name.split(".")
        assert head in modules and stages, (name, f)


# ---------------------------------------------------------------------------
# timing keys are the durations of their spans
# ---------------------------------------------------------------------------


def _calls(root_name):
    """Spans of the newest call whose outermost span is ``root_name``."""
    root = [s for s in obs.RECORDER.spans if s.name == root_name][-1]
    return root, [s for s in obs.RECORDER.spans if s.root == root.root]


def _sum(spans, name):
    return sum(s.seconds for s in spans if s.name == name)


@pytest.fixture(scope="module")
def routed():
    return route_pod(T.pt((4, 4, 4)), PipelineConfig(K=4))


def test_route_pod_timings_are_its_spans(routed):
    route, calls = _calls("pipeline.route_pod")
    by = {s.name: s for s in calls if s.parent == route.id}
    assert set(by) == {"routing.allowed_turns", "routing.select",
                       "pipeline.vc"}
    assert routed.timings == {"at_s": by["routing.allowed_turns"].seconds,
                              "select_s": by["routing.select"].seconds,
                              "vc_s": by["pipeline.vc"].seconds}


def test_sharded_selection_stats_are_its_spans(routed):
    _, calls = _calls("pipeline.route_pod")
    st = routed.routed.stats
    select = next(s for s in calls if s.name == "routing.select")
    bfs = next(s for s in calls if s.name == "routing.select.bfs")
    assert bfs.parent == select.id
    assert st["bfs_s"] == bfs.seconds
    assert st["uniq_s"] == _sum(calls, "routing.select.bfs.uniq") > 0
    for key in ("walk", "greedy", "refine"):
        assert st[f"{key}_s"] == _sum(calls, f"routing.select.{key}")
    # one walk and one greedy per pass of a round over a shard
    passes = st["rounds"] * -(-64 // st["shard_sources"])
    assert len([s for s in calls if s.name == "routing.select.walk"]) \
        == passes
    parts = sum(st[f"{k}_s"] for k in ("bfs", "walk", "greedy", "refine"))
    assert parts <= select.seconds


@pytest.fixture(scope="module")
def served():
    topo = T.pdtt((4, 4, 4))
    return topo, ServingState.build(topo, n_vc=4, K=8, seed=0, robust=True)


RESELECT = ("walk", "bfs", "readmit", "greedy", "refine")


def test_repair_stats_are_its_spans(served):
    topo, st = served
    dead = F.dead_channels_for_color(st.at, F.colors_in_use(topo)[0])
    rr = repair_fault(st, dead)
    root, calls = _calls("repair.repair_fault")
    assert rr.stats["total_s"] == root.seconds
    for key in ("prune", "vc", "verify") + RESELECT:
        assert rr.stats[f"{key}_s"] == _sum(
            calls, f"repair.repair_fault.{key}"), key
    assert rr.stats["walk_s"] > 0 and rr.stats["vc_s"] > 0
    # the five re-selection stages that reselect_ms.recover sums
    assert sum(rr.stats[f"{k}_s"] for k in RESELECT) == pytest.approx(
        sum(s.seconds for s in calls
            if s.name in {f"repair.repair_fault.{k}" for k in RESELECT}),
        rel=1e-12)
    assert all(s.parent == root.id for s in calls if s is not root)

    heal = restore_channels(rr.state, dead)
    hroot, hcalls = _calls("repair.heal")
    assert heal.stats["total_s"] == hroot.seconds
    for key in ("readmit", "bfs", "walk", "greedy", "refine", "vc",
                "verify"):
        assert heal.stats[f"{key}_s"] == _sum(hcalls, f"repair.heal.{key}")


def test_fallback_stage_is_a_span(served):
    topo, st = served
    # every channel of node 0 dies: node 0 is cut off and the
    # recompute policy re-selects everything
    ch = st.at.channels
    dead = np.nonzero((ch.src == 0) | (ch.dst == 0))[0].astype(np.int64)
    rr = repair_fault(st, dead, on_disconnect="recompute")
    assert rr.fallback
    _, calls = _calls("repair.repair_fault")
    assert rr.stats["fallback_s"] == _sum(calls,
                                          "repair.repair_fault.fallback")
    # the re-selection inside it records its own stages under it
    fb = next(s for s in calls if s.name == "repair.repair_fault.fallback")
    assert any(s.name == "routing.select.bfs" and s.parent == fb.id
               for s in calls)


def test_no_program_span_is_named_like_the_harness(routed):
    assert not [s.name for s in obs.RECORDER.spans
                if s.name.startswith("bench.")]
    assert {s.name.split(".")[0] for s in obs.RECORDER.spans} <= \
        {"pipeline", "routing", "netsim", "traffic", "repair"}
