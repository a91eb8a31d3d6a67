"""The expert-parallel decode demand (``workload.ep_decode_trace``)
against the benchmark's plain token-by-token router."""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import obs, workload as W

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.reference import ep_route as ER  # noqa: E402

V3 = W.ROUTERS["DeepSeek-V3"]
# a 32-chip pod, two experts per chip: 64 experts in 8 groups of 8
SMALL = dataclasses.replace(V3, n_routed_experts=64)


def params(spec, tokens_per_chip, experts_per_chip, period):
    return {"published": {"n_routed_experts": spec.n_routed_experts,
                          "n_group": spec.n_group,
                          "topk_group": spec.topk_group,
                          "num_experts_per_tok": spec.experts_per_token},
            "tokens_per_chip": tokens_per_chip,
            "experts_per_chip": experts_per_chip, "period": period,
            "bytes_per_copy": {"dispatch": 7392, "combine": 14336}}


@pytest.mark.parametrize("dims,spec,tpc,layers", [
    ((4, 4, 2), SMALL, 6, 3),
    ((4, 4, 8), V3, 2, 2),
])
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11])
def test_ep_decode_trace_matches_plain_router(dims, spec, tpc, layers, seed):
    n = int(np.prod(dims))
    period = 256
    since = len(obs.RECORDER.counts)
    trace = W.ep_decode_trace(dims, spec, tokens_per_chip=tpc,
                              layers=layers, period=period, seed=seed,
                              experts_per_chip=2)
    counts = {c.name: c.value for c in list(obs.RECORDER.counts)[since:]}
    p = params(spec, tpc, 2, period)
    routes = ER.route(p, n * tpc, layers, seed)
    copies = ER.copies(p, routes, n)
    phases, cycles = ER.phases(p, copies, n)

    # bit for bit: routes, matrices, rates, cycles
    got = np.sort(W.route_tokens(spec, n * tpc, layers, seed), axis=2)
    assert np.array_equal(got, np.asarray(routes))
    assert list(trace.cycles) == cycles
    assert len(trace.patterns) == 2 * layers == len(phases)
    for pat, (m, r) in zip(trace.patterns, phases):
        assert np.array_equal(pat.matrix, m)
        assert pat.src_rate.dtype == np.float32
        assert np.array_equal(pat.src_rate, r)

    # every token reaches at most topk_group groups
    width = spec.n_routed_experts // spec.n_group
    assert all(len({e // width for e in s}) <= spec.topk_group
               for sets in routes for s in sets)
    # two experts on one chip make one copy; none to the token's own chip
    for sets, c in zip(routes, copies):
        want = sum(len({e // 2 for e in s} - {t // tpc})
                   for t, s in enumerate(sets))
        assert c.sum() == want
        assert np.trace(c) == 0
    # combine is dispatch transposed, at 14,336 / 7,392 of its bytes
    for d, c in zip(trace.patterns[0::2], trace.patterns[1::2]):
        assert np.array_equal(c.matrix * 7392, d.matrix.T * 14336)
    assert trace.period == period and min(trace.cycles) >= 1
    # the counters are the matrices' sums
    dispatch = np.stack([pat.matrix for pat in trace.patterns[0::2]]) / 7392
    cube = np.array([((i % dims[0]) // 4, (i // dims[0] % dims[1]) // 4,
                      (i // (dims[0] * dims[1])) // 4) for i in range(n)])
    cross = (cube[:, None, :] != cube[None, :, :]).any(axis=2)
    assert counts["workload.ep.copies"] == dispatch.sum()
    assert counts["workload.ep.cross_cube_copies"] == dispatch[:, cross].sum()
    assert counts["workload.ep.max_in_copies"] == dispatch.sum(axis=1).max()
    assert counts["workload.ep.mean_in_copies"] == \
        int(dispatch.sum()) // (n * layers)
