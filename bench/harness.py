"""One run of one benchmark cell.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness finds everything else by those names:

- ``bench/configs/<config>.json``: the deployment (fabric, pod shape,
  routing settings), read by the reference as well as by the units;
- ``bench/traffic/<traffic>.json``: the mix, whose ``unit`` names the
  timed unit and whose other keys are that unit's parameters;
- ``bench/units/<unit>.py``: ``setup``, ``run`` and ``check`` of one
  kind of timed unit;
- ``bench/patterns/<pattern>.py``: one traffic pattern, the program's
  constructor and the reference's demand matrix;
- ``bench/fabrics/<fabric>.py``: one fabric kind, the program's
  ``Topology`` and the reference's optical links;
- ``bench/metrics/<metric>.py`` and ``bench/layers/<metric>.py``: one
  reader per end-to-end and per-layer metric, ``read(run)`` returning a
  number or None (nothing to read: the metric is left out).

A run is set-up (imports, the unit's set-up and one warm unit), then a
window in which units run back to back until ``seconds`` have passed
(the unit running at the close completes and counts), then the check of
what the window produced against the plain reference: ``CHECKED`` of
the window's units, drawn from the seed (:func:`sampled`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import random
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Callable, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = BENCH / ".trace"   # one traced run at a time per checkout
STORE = BENCH / ".store"       # routed tables kept between runs


class NoChip(RuntimeError):
    pass


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def read_json(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported once."""
    mod = sys.modules.get(f"bench.{kind}.{name}")
    if mod is not None:
        return mod
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_inputs(bench: dict, cell: str, config: Optional[dict] = None,
                traffic: Optional[dict] = None):
    """The cell's ``workloads`` entry, configuration and traffic mix."""
    w = next((x for x in bench["workloads"] if x["name"] == cell), None)
    if w is None:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    if config is None:
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        config = json.loads((ROOT / entry["file"]).read_text())
    return w, config, traffic or read_json("traffic", w["traffic"])


def metrics_of(bench: dict, section: str, cell: str) -> List[dict]:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


class Spans:
    """Host spans around the benchmark's calls into the program: kept in
    memory as (name, start, seconds) and written into the profiler's
    trace as ``bench.<name>`` so that idle gaps can be named."""

    def __init__(self):
        self.records: list = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.records.append((name, t, time.perf_counter() - t))

    def seconds(self, name: str) -> List[float]:
        return [s for k, _, s in self.records if k == name]


class CompileClock:
    """Counts JAX's backend compiles (a persistent-cache hit reports
    one too, with its load time) and sums their seconds."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def kernel_seed(seed: int, index: int) -> int:
    """A 31-bit seed for unit ``index`` of a run drawn with ``seed``."""
    return (seed * 0x9E3779B1 + index) % (2 ** 31 - 1)


@dataclasses.dataclass
class Run:
    """What a metric reader and a unit's check read of one run."""
    seed: int
    config: dict
    traffic: dict
    state: Any
    outputs: list          # each unit's output, None where it raised
    unit_s: List[float]
    window_s: float
    spans: Spans
    trace: Optional[dict] = None   # bench.trace.reduce() of a traced run


def per_unit(run: Run) -> float:
    """Wall seconds per unit: the whole window over the units in it."""
    return run.window_s / len(run.unit_s)


CHECKED = 2   # units of a run that the reference recomputes


def sampled(run: Run) -> list:
    """(index, output) of the units the check compares: ``CHECKED`` of
    the window's units that returned, drawn from the run's seed."""
    done = [i for i, o in enumerate(run.outputs) if o is not None]
    pick = random.Random(run.seed).sample(done, min(CHECKED, len(done)))
    return [(i, run.outputs[i]) for i in sorted(pick)]


@contextlib.contextmanager
def _profiler(on: bool, found: dict):
    if not on:
        yield
        return
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
        yield
    found["files"] = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        t0: Optional[float] = None, chip_check: bool = True,
        bench: Optional[dict] = None, config: Optional[dict] = None,
        traffic: Optional[dict] = None,
        log: Callable[[str], None] = print):
    """One run of ``cell``; returns the result object that the command
    prints last, and the checks (name, value, limit) behind
    ``correct``. Raises :class:`NoChip` before any work when the chip
    check is on and JAX's devices are not the cell's TPUs."""
    t0 = time.perf_counter() if t0 is None else t0
    bench = bench or load_benchmark()
    w, config, traffic = cell_inputs(bench, cell, config, traffic)

    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    if chip_check and (dev.platform != "tpu" or len(devs) < w["chips"]):
        raise NoChip(f"cell {cell} needs {w['chips']} TPU chip(s); JAX "
                     f"sees {len(devs)} {dev.platform} device(s)")
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} compile_cache={cache_dir}")
    clock = CompileClock()
    unit = load_module("units", traffic["unit"])
    spans = Spans()
    state = unit.setup(config, traffic, seed, spans)
    setup_s = time.perf_counter() - t0
    compiles = clock.count
    # the first run of a cell in a checkout routes and compiles what
    # later runs read back: its set-up is reported apart
    marker = STORE / f"ran-{cell}"
    first = not marker.exists()
    STORE.mkdir(parents=True, exist_ok=True)
    marker.touch()
    log(f"setup seconds={setup_s} first_in_checkout={first} "
        f"compiles={clock.count} "
        f"compile_s={clock.seconds} cache_hits={clock.cache_hits} "
        f"spans={[(k, s) for k, _, s in spans.records]}")

    spans.records.clear()
    outputs, unit_s, failed = [], [], 0
    found: dict = {}
    with _profiler(trace, found), spans("window"):
        w0 = time.perf_counter()
        while True:
            u0 = time.perf_counter()
            try:
                with spans("unit"):
                    outputs.append(unit.run(state, len(unit_s), spans))
            except Exception as e:  # a failed unit counts, the run goes on
                failed += 1
                outputs.append(None)
                log(f"unit {len(unit_s)} failed: {e!r}")
            unit_s.append(time.perf_counter() - u0)
            if time.perf_counter() - w0 >= seconds:
                break
        window_s = time.perf_counter() - w0
    log(f"window seconds={window_s} units={len(unit_s)} failed={failed} "
        f"compiles_inside={clock.count - compiles} unit_s={unit_s}")

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    r = Run(seed, config, traffic, state, outputs, unit_s, window_s, spans)
    result: dict = {}
    if trace:
        from bench import trace as tr
        t = time.perf_counter()
        r.trace = tr.reduce(tr.load(found["files"][0]))
        log(f"trace file_bytes={found['files'][0].stat().st_size} "
            f"read_s={time.perf_counter() - t}")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = r.trace["busy_s"]
        device["window_s"] = r.trace["window_s"]
        result["breakdown"] = {"device_ops": r.trace["device_ops"],
                               "idle_gaps": r.trace["idle_gaps"]}
        log(f"trace programs={r.trace['programs']}")
    metrics = {}
    section = "per_layer" if trace else "end_to_end"
    for m in metrics_of(bench, section, cell):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = load_module("metrics" if section == "end_to_end"
                                else "layers", m["name"]).read(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    try:
        checks = unit.check(r)
    except Exception:  # a check that cannot judge the output fails it
        log(traceback.format_exc())
        checks = [("check_raised", 1, 0)]
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)
    result = {"correct": correct, "attempted": len(unit_s),
              "failed": failed, "metrics": metrics, "device": device,
              **result, "first_in_checkout": first,
              "checks": {k: {"value": v, "limit": lim}
                         for k, v, lim in checks}}
    return result, checks


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    log = lambda s: print(s, flush=True)  # noqa: E731
    try:
        result, checks = run(a.workload, a.seed, a.seconds, bool(a.trace),
                             t0=t0, log=log)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 1
    for k, v, lim in checks:
        print(f"check {k} value={v} limit={lim} ok={v <= lim}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
