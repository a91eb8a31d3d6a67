"""The sweep kernel's budget of gathered and scattered indices per cycle.

On a TPU an XLA gather or scatter costs about the same per index
whatever the size of its table, so the cycle body's time follows the
number of indices it gathers and scatters. Indices that the queue layout
or the loop fixes (one of ``n_vc`` VCs per channel, each ring's head
slot, the pops, one of ``D`` adaptive candidates, tables that do not
change inside the loop) are dense selects or precomputed before the
loop; only indices that depend on the data are gathered or scattered.

The budget counts, in ``_sweep_csr`` lowered for a 4x4x8 pod, the
indices of every ``stablehlo.gather`` and ``stablehlo.scatter`` inside
the loop body, by the named scope of the cycle phase that holds it.
The counts of the kernel before the layout-fixed indices left the body,
per cycle:

- static, ``n_vc=2``, 4 rates (C = 3,072 channels, NQ = 6,144 queues,
  N = 512 sources): 77,824 = route 30,720 + arbitrate 15,360 + crossbar
  6,144 + push 6,144 + inject 6,144 + scatter 13,312;
- adaptive escape-VC with a fault, ``n_vc=4``, 3 rates (C = 2,304,
  NQ = 9,216, D = 6 candidates, N = 384): 326,784 = route 267,264 +
  arbitrate 16,128 + crossbar 4,608 + push 4,608 + inject 5,760 +
  scatter 9,984 + counters 18,432.

These shapes are those of the benchmark's ``tons-128`` sweep, and of its
``pt-8x8x8`` sweep at a quarter of the channels.
"""
import re
from collections import Counter

import pytest

from repro.core import fault as F, netsim as NS, routing as R, \
    topology as T

POD = (4, 4, 8)
SCOPES = ("route", "arbitrate", "crossbar", "push", "inject", "scatter",
          "counters", "watchdog")


def _ops(op, funcs, site=None):
    """(operation, location) of every operation nested in ``op``, through
    the private functions that nested jits lower to; an operation inside
    a called function takes the location of the outermost call."""
    for region in op.regions:
        for block in region.blocks:
            for o in block.operations:
                if o.operation.name == "func.call":
                    callee = funcs[str(o.attributes["callee"]).lstrip("@")]
                    yield from _ops(callee, funcs, site or o.location)
                else:
                    yield o, site or o.location
                    yield from _ops(o, funcs, site)


def _shape(value):
    return [int(d) for d in re.findall(r"(\d+)x", str(value.type))]


def _n_indices(op):
    """Index vectors an XLA gather or scatter reads: its index array's
    elements over the length of one index vector."""
    dims = str(op.attributes["dimension_numbers" if op.operation.name.endswith(
        "gather") else "scatter_dimension_numbers"])
    vdim = int(re.search(r"index_vector_dim = (\d+)", dims).group(1))
    shape = _shape(op.operands[1])
    n = 1
    for d in shape:
        n *= d
    return n // shape[vdim] if vdim < len(shape) else n


def _body_ops(case):
    """(scope, op name, indices, operand shape) of each gather and
    scatter in the cycle body of the case's kernel."""
    topo = T.pt(POD)
    if case == "static":
        tab = NS.dor_tables(topo)
        rates, kw = [0.05, 0.1, 0.2, 0.4], {"adaptive": None, "fault": None}
    else:
        tab = NS.dor_tables(topo, n_vc=4)
        at = R.allowed_turns(topo, n_vc=2, priority="apl")
        ev = F.fault_event(at, F.colors_in_use(topo)[0], 800)
        rates = [0.05, 0.2, 0.4]
        kw = {"adaptive": NS.adaptive_spec(topo, dead_channels=ev[1]),
              "fault": ev}
    call = NS._sweep_call(tab, rates, None, cycles=1500, warmup=500,
                          slots=128, seed=0, flits=4, kernel="csr",
                          patience=64, watchdog=512, **kw)
    module = call.fn.lower(*call.args, **call.static).compiler_ir(
        "stablehlo")
    funcs = {str(f.attributes["sym_name"]).strip('"'): f
             for f in module.body.operations
             if f.operation.name == "func.func"}
    found = []
    for op, loc in _ops(funcs["main"], funcs):
        kind = op.operation.name
        if kind not in ("stablehlo.gather", "stablehlo.scatter"):
            continue
        scope = re.search(r"while/body/(\w+)", str(loc))
        if scope:
            found.append((scope.group(1), kind.split(".")[1],
                          _n_indices(op), _shape(op.operands[0])))
    return tab, len(rates), call.static, found


# indices per cycle now (before: in the module docstring)
BUDGET = {"static": 48_128, "adaptive-fault": 140_928}


@pytest.mark.parametrize("case", ["static", "adaptive-fault"])
def test_cycle_body_gathers_only_what_the_data_decides(case):
    tab, n_rates, static, found = _body_ops(case)
    NQ = n_rates * tab.n_ch * tab.n_vc
    per_scope = Counter()
    for scope, _, k, _ in found:
        assert scope in SCOPES, scope
        per_scope[scope] += k
    # picking one VC per channel and counting pops is dense
    assert per_scope["arbitrate"] == 0
    assert per_scope["counters"] == 0
    # each ring's head slot is selected, not gathered
    assert not [f for f in found if f[3] == [NQ, static["slots"]]
                and f[1] == "gather"]
    # no gather per (queue, adaptive candidate): the candidates and
    # their liveness are fixed before the loop, and their occupancy is
    # read once per (lane, node, candidate)
    assert max(f[2] for f in found if f[1] == "gather") <= NQ
    assert sum(per_scope.values()) <= BUDGET[case]
