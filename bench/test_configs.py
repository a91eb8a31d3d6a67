"""The configuration files and the reference's view of them agree with
the program's own fabrics."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import deploy
from bench.reference import fabric as RF, netsim_ref as RN

ROOT = Path(__file__).resolve().parents[1]


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_tons_json_matches_recorded_fabric():
    sys.path.insert(0, str(ROOT))
    from benchmarks.common import load_tons
    topo, rec = load_tons(128)
    got = deploy.topology(config("tons-128"))
    assert got.pod == topo.pod
    assert got.optical == topo.optical
    assert np.array_equal(got.edges(), topo.edges())
    assert np.array_equal(got.edge_colors(), topo.edge_colors())
    c = config("tons-128")["recorded"]
    assert (c["mcf"], c["diam"], c["hops"]) == (rec["mcf"], rec["diam"],
                                                rec["hops"])


@pytest.mark.parametrize("name,pod", [("pt-8x8x8", None),
                                      ("tons-128", None),
                                      ("pt-8x8x8", [4, 4, 8]),
                                      ("pt-8x8x8", [4, 4, 4])])
def test_reference_channels_match_program(name, pod):
    from repro.core.routing import Channels
    cfg = dict(config(name), **({"pod": pod} if pod else {}))
    fab = RF.fabric(cfg)
    ch = Channels.from_topology(deploy.topology(cfg))
    assert np.array_equal(ch.src, fab.src)
    assert np.array_equal(ch.dst, fab.dst)
    assert np.array_equal(ch.color, fab.color)


@pytest.mark.parametrize("n", [128, 512])
@pytest.mark.parametrize("traffic", [{"pattern": "uniform"},
                                     {"pattern": "hotspot", "frac": 0.4,
                                      "hot": [0]}])
def test_reference_alias_tables_match_program(traffic, n):
    from repro.core.traffic import compile_flow_traffic
    from bench.harness import load_module
    # every ordered pair of distinct chips routed, in row-major order
    src_indptr = np.arange(n + 1) * (n - 1)
    dst = np.array([d for s in range(n) for d in range(n) if d != s])
    pattern = load_module("patterns", traffic["pattern"]).program(traffic, n)
    ct = compile_flow_traffic(pattern, src_indptr, dst)
    m, rate = RN.demand(traffic, n)
    empty = np.zeros(0, np.int64)
    t = RF.Table(src_indptr, dst, np.zeros(len(dst) + 1, np.int64), empty,
                 empty, 2)
    prob, alias = RN.flow_alias(m, t)
    assert np.array_equal(prob, ct.prob)
    assert np.array_equal(alias, ct.alias)
    assert np.array_equal(rate, ct.src_rate)
