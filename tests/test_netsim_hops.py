"""The sweep kernels' packet-hop counter and their device program names.

A packet-hop is one pop from a channel queue: the packet moves on to its
next channel or is consumed. Both kernels count it per rate lane
(``hops``), bit-identically on the static, adaptive and fault paths; a
packet consumed has been popped once per channel it crossed, so the
count bounds ``consumed_total`` from above and equals it when every
route is one hop long. The kernels agree on every counter with each of
the CSR kernel's flags, on the four-VC queue layout and on the two-VC
one, so that the kernel's pick of one VC per channel sees more than two
VCs.
"""
import numpy as np
import pytest

from repro.core import fault as F, netsim as NS, routing as R, \
    topology as T
from repro.core.pathtable import CSRPathTable
from repro.core.traffic import (PhasedTraffic, TenantSpec, TrafficPattern,
                                compose_tenants)

SHORT = dict(cycles=400, warmup=100)
RATES = [0.05, 0.3]


def _routed(n_vc):
    topo = T.pt((4, 4, 4))
    at = R.allowed_turns(topo, n_vc=n_vc, priority="robust")
    sel = R.select_paths(at, K=4, local_search_rounds=1, engine="sharded")
    return topo, at, NS.at_tables(topo, at, sel, reserve_escape=True)


@pytest.fixture(scope="module")
def pod():
    return _routed(4)


@pytest.fixture(scope="module")
def pod2():
    return _routed(2)


def _traffic(case, n):
    if case == "bursty":
        return TrafficPattern.uniform(n).with_burst(64, duty=0.25, gain=3.0)
    if case == "tenants":
        rng = np.random.default_rng(0)
        half = n // 2
        return compose_tenants(n, [
            TenantSpec("a", np.arange(half), rng.random((half, half))),
            TenantSpec("b", np.arange(half - 8, n),
                       rng.random((n - half + 8, n - half + 8)), 0.5)])
    return PhasedTraffic("two", (TrafficPattern.uniform(n),
                                 TrafficPattern.hotspot(n, frac=0.4)),
                         (64, 96))


def _kw(case, topo, at):
    if case == "static":
        return {}
    if case in ("bursty", "tenants", "phased"):
        return {"traffic": _traffic(case, topo.n)}
    if case == "adaptive":
        return {"adaptive": NS.adaptive_spec(topo)}
    ev = F.fault_event(at, F.colors_in_use(topo)[0], 150)
    if case == "fault":
        return {"fault": ev}
    return {"fault": ev,
            "adaptive": NS.adaptive_spec(topo, dead_channels=ev[1])}


FLAGS = ["static", "fault", "adaptive", "adaptive-fault", "bursty",
         "tenants", "phased"]


@pytest.mark.parametrize(
    "case", ["static", "fault", "adaptive-fault", "adaptive", "bursty",
             "tenants", "phased"] + [f"2vc-{f}" for f in FLAGS])
def test_hops_bit_identical_across_kernels(pod, pod2, case):
    topo, at, tab = pod2 if case.startswith("2vc-") else pod
    assert tab.n_vc == (2 if case.startswith("2vc-") else 4)
    kw = _kw(case.removeprefix("2vc-"), topo, at)
    tc = NS.sweep(tab, RATES, kernel="csr", seed=7, **SHORT, **kw)
    td = NS.sweep(tab, RATES, kernel="dense", seed=7, **SHORT, **kw)
    assert [r["hops"] for r in tc] == [r["hops"] for r in td]
    assert tc == td
    for r in tc:
        assert r["hops"] >= r["consumed_total"] > 0


@pytest.mark.parametrize("case", ["static", "adaptive-fault"])
def test_hops_repeat_under_a_seed(pod, case):
    topo, at, tab = pod
    kw = _kw(case, topo, at)
    a = NS.sweep(tab, RATES, seed=2 ** 31 - 5, **SHORT, **kw)
    b = NS.sweep(tab, RATES, seed=2 ** 31 - 5, **SHORT, **kw)
    assert [r["hops"] for r in a] == [r["hops"] for r in b]
    c = NS.sweep(tab, RATES, seed=3, **SHORT, **kw)
    assert [r["hops"] for r in a] != [r["hops"] for r in c]


def _one_hop(topo, tab):
    """The routed table cut to its one-hop flows."""
    t = tab.csr()
    keep = np.nonzero(t.flow_len == 1)[0]
    src_indptr = np.zeros(t.n + 1, np.int64)
    np.cumsum(np.bincount(t.flow_src[keep], minlength=t.n),
              out=src_indptr[1:])
    first = t.hop_indptr[keep]
    one = CSRPathTable(t.n, t.n_ch, t.n_vc, src_indptr, t.dst[keep].copy(),
                       np.arange(len(keep) + 1, dtype=np.int64),
                       t.chan[first].copy(), t.vc[first].copy())
    return NS.build_tables(topo, one)


@pytest.mark.parametrize("kernel", ["csr", "dense"])
def test_hops_equal_consumed_when_every_route_is_one_hop(pod, kernel):
    topo, _, tab = pod
    one = _one_hop(topo, tab)
    assert (one.csr().flow_len == 1).all()
    for r in NS.sweep(one, RATES, kernel=kernel, **SHORT):
        assert r["hops"] == r["consumed_total"] > 0


def test_hops_zero_when_no_flow_is_routed(pod):
    topo, _, tab = pod
    t = tab.csr()
    empty = CSRPathTable(t.n, t.n_ch, t.n_vc, np.zeros(t.n + 1, np.int64),
                         np.zeros(0, np.int32), np.zeros(1, np.int64),
                         np.zeros(0, np.int32), np.zeros(0, np.int8))
    lanes = NS.sweep(NS.build_tables(topo, empty), RATES, **SHORT)
    assert [r["hops"] for r in lanes] == [0, 0]


@pytest.mark.parametrize("kernel", ["csr", "dense"])
def test_kernel_program_names_and_phase_scopes(pod, kernel):
    """The exported names are the modules the kernels lower to, and the
    cycle body's phases name the operations inside them."""
    _, _, tab = pod
    call = NS._sweep_call(tab, RATES, None, slots=128, seed=0, flits=4,
                          kernel=kernel, adaptive=None, fault=None,
                          patience=64, watchdog=512, **SHORT)
    lowered = call.fn.lower(*call.args, **call.static)
    name = f"jit__sweep_{kernel}"
    assert name in NS.KERNEL_PROGRAMS
    assert f"module @{name} " in lowered.as_text()
    text = lowered.as_text(debug_info=True)
    for scope in ("route", "arbitrate", "crossbar", "push", "inject",
                  "scatter", "counters", "watchdog"):
        assert f"while/body/{scope}/" in text, scope
