"""Plain reference for the cycle-level network simulator.

It imports nothing of the program under test. From a configuration's
fabric (``fabric.Fabric``), a routed path table, a traffic mix's
parameters and a seed it recomputes, one cycle at a time with NumPy,
every counter that a sweep reports for each injection rate.

The semantics it implements, which a sweep must reproduce bit for bit:

- every (rate lane, channel, VC) is a FIFO of ``slots`` packets; a
  packet is one word holding its flow id, hop index and a tag bit that
  marks injection inside the measured window;
- each cycle every channel that is not serialising a multi-flit packet
  grants one VC round-robin among queues whose head can move (consume
  at its destination, or forward to a queue with room); a target queue
  accepts one forward per cycle, lowest channel id first;
- each source draws three uniforms: inject if the first is below
  ``rate * src_rate``; the second picks a flow slot, the third accepts
  it or takes its alias (Vose tables over the source's routed flows);
  the injection lands in the flow's first-hop queue if it has room
  after this cycle's pops and pushes;
- random bits are JAX's threefry stream: ``PRNGKey(seed)``, split into
  four each cycle (carry, and the three draws);
- adaptive routing (VC 0 is the escape lane over a BFS spanning tree
  rooted at node 0; other VCs follow the planned path while its
  destination-bound queue has room, else the freest minimal
  alternate), and a mid-sweep fault that kills channels at a cycle;
- a lane that has packets in flight and neither pops nor injects for
  ``watchdog`` cycles is stalled; the sweep stops when all are.

``dtype`` sets the precision of the injection arithmetic (threshold,
flow-slot pick and alias accept). The configurations state float32;
``"bfloat16"`` is the lower-precision control.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csg

from bench.reference.fabric import Fabric, Table

FLOW_MASK = (1 << 24) - 1
HOP_SHIFT, HOP_MASK, TAG_SHIFT = 24, (1 << 6) - 1, 30


# ---- traffic -------------------------------------------------------------

def demand(params: dict, n: int):
    """(n, n) float64 demand and (n,) float32 source intensity of a
    traffic mix, from its pattern's ``bench/patterns/<pattern>.py``. The
    float64 expressions there are the pattern's definition: a different
    rounding would pick other alias thresholds."""
    from bench.harness import load_module
    return load_module("patterns", params["pattern"]).demand(params, n)


def flow_alias(m: np.ndarray, t: Table):
    """Vose alias tables over each source's routed flows: (F,) float32
    accept probability and (F,) alias flow id. Small and large entries
    are stacks in ascending slot order, popped from the top."""
    F = len(t.dst)
    prob = np.ones(F, np.float32)
    alias = np.arange(F, dtype=np.int64)
    for s in range(len(t.src_indptr) - 1):
        f0, f1 = int(t.src_indptr[s]), int(t.src_indptr[s + 1])
        if f1 == f0:
            continue
        w = m[s, t.dst[f0:f1]]
        total = w.sum(dtype=np.float64)
        if total <= 0:
            prob[f0:f1] = 0.0
            continue
        q = w * (np.float64(f1 - f0) / total)
        small = [j for j in range(f1 - f0) if q[j] < 1.0]
        large = [j for j in range(f1 - f0) if q[j] >= 1.0]
        while small and large:
            s_, l_ = small.pop(), large[-1]
            prob[f0 + s_] = q[s_]
            alias[f0 + s_] = f0 + l_
            q[l_] = q[l_] - (1.0 - q[s_])
            if q[l_] < 1.0:
                small.append(large.pop())
    return prob, alias


# ---- adaptive routing tables ---------------------------------------------

def _live_graph(fab: Fabric, alive: np.ndarray):
    return sp.csr_matrix((np.ones(int(alive.sum()), np.float32),
                          (fab.src[alive], fab.dst[alive])),
                         shape=(fab.n, fab.n))


def escape_next(fab: Fabric, alive: np.ndarray) -> np.ndarray:
    """(n, n) next channel from u toward d along the BFS spanning tree
    of the live fabric rooted at node 0; -1 on the diagonal or where
    the tree does not reach."""
    n = fab.n
    tree = csg.breadth_first_tree(_live_graph(fab, alive), 0,
                                  directed=False)
    tr, tc = tree.nonzero()
    und = sp.csr_matrix((np.ones(len(tr), np.float32), (tr, tc)),
                        shape=(n, n))
    _, pred = csg.shortest_path(und + und.T, unweighted=True,
                                return_predecessors=True)
    nxt = pred.T
    chan_of = np.full((n, n), -1, np.int64)
    chan_of[fab.src[alive], fab.dst[alive]] = np.nonzero(alive)[0]
    u = np.repeat(np.arange(n), n).reshape(n, n)
    out = np.where(nxt >= 0, chan_of[u, np.clip(nxt, 0, n - 1)], -1)
    np.fill_diagonal(out, -1)
    return out


def out_channels(fab: Fabric) -> np.ndarray:
    """(n, D) channels leaving each node in channel-id order, -1 pad."""
    order = np.argsort(fab.src, kind="stable")
    deg = np.bincount(fab.src, minlength=fab.n)
    out = np.full((fab.n, int(deg.max())), -1, np.int64)
    slot = np.arange(fab.n_ch) - np.repeat(np.cumsum(deg) - deg, deg)
    out[fab.src[order], slot] = order
    return out


def minimal_mask(fab: Fabric, alive: np.ndarray,
                 outch: np.ndarray) -> np.ndarray:
    """(n, n) bit j set iff out-channel j of u is live and lies on a
    shortest live path from u to d."""
    d = csg.shortest_path(_live_graph(fab, alive), method="D",
                          unweighted=True)
    dist = np.where(np.isinf(d), -1, d).astype(np.int64)
    mask = np.zeros((fab.n, fab.n), np.int64)
    for j in range(outch.shape[1]):
        c = outch[:, j]
        cc = np.clip(c, 0, fab.n_ch - 1)
        ok = (c >= 0) & alive[cc]
        dn = dist[fab.dst[cc]]
        mask |= ((ok[:, None] & (dn >= 0) & (dist == dn + 1))
                 .astype(np.int64) << j)
    return mask


def adaptive_tables(fab: Fabric, dead: Optional[np.ndarray]):
    """Escape and minimal-alternate tables, stacked (pre-fault,
    post-fault), and the out-channel slot layout."""
    alive0 = np.ones(fab.n_ch, bool)
    alive1 = alive0.copy()
    if dead is not None:
        alive1[dead] = False
    outch = out_channels(fab)
    esc = np.stack([escape_next(fab, alive0), escape_next(fab, alive1)])
    mm = np.stack([minimal_mask(fab, alive0, outch),
                   minimal_mask(fab, alive1, outch)])
    return esc, outch, mm


# ---- random bits ---------------------------------------------------------

def uniforms(seed: int, cycles: int, N: int) -> np.ndarray:
    """(cycles, 3, N) float32 draws of the threefry stream, on JAX's
    CPU backend so that the reference never touches the accelerator."""
    import jax
    import jax.numpy as jnp

    def step(key, _):
        key, k1, k2, k3 = jax.random.split(key, 4)
        return key, jnp.stack([jax.random.uniform(k, (N,))
                               for k in (k1, k2, k3)])

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.random.PRNGKey(seed)
        _, u = jax.jit(lambda k: jax.lax.scan(step, k, None,
                                              length=cycles))(key)
        return np.asarray(u)


# ---- the simulator -------------------------------------------------------

def simulate(fab: Fabric, t: Table, traffic: dict,
             rates: Sequence[float], seed: int, *, cycles: int,
             warmup: int, slots: int, flits: int,
             adaptive: bool = False, fault: Optional[tuple] = None,
             patience: int = 64, watchdog: int = 512,
             dtype: str = "float32"):
    """Every rate lane's counters, as a list of dicts (the sweep's own
    layout), and the number of cycles run."""
    if dtype == "float32":
        def lo(x):
            return np.asarray(x, np.float32)
    elif dtype == "bfloat16":
        import ml_dtypes

        def lo(x):
            return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
                .astype(np.float32)
    else:
        raise ValueError(f"unknown dtype {dtype!r}")
    n, n_ch, n_vc = fab.n, fab.n_ch, t.n_vc
    if len(t.dst) == 0:   # nothing routed: nothing is ever injected
        zero = {"offered": 0.0, "accepted": 0.0, "delivered": 0.0,
                "delivered_tagged": 0.0, "consumed_total": 0,
                "injected_total": 0, "in_flight": 0, "escaped": 0,
                "stalled_at": -1}
        return [dict(zero, rate=float(np.float32(r))) for r in rates], cycles
    m, src_rate = demand(traffic, n)
    fprob, falias = flow_alias(m, t)
    rates = np.asarray(list(rates), np.float32)
    R = len(rates)
    C, NQ, N = R * n_ch, R * n_ch * n_vc, R * n
    pvf = t.chan * n_vc + t.vc
    hptr = t.hop_indptr[:-1]
    lenm1 = np.diff(t.hop_indptr) - 1
    H = len(pvf)
    deg = np.diff(t.src_indptr)
    H_F = len(t.dst)
    src_ptr = t.src_indptr[:-1]
    ch_dst = fab.dst

    faulted = fault is not None
    t_fault, dead = (int(fault[0]), np.asarray(fault[1], np.int64)) \
        if faulted else (0, None)
    alive = np.ones((2, n_ch), bool)
    if faulted:
        alive[1, dead] = False
    if adaptive:
        esc, outch, minmask = adaptive_tables(fab, dead)
        D = outch.shape[1]

    q = np.zeros((NQ, slots), np.int64)
    head = np.zeros(NQ, np.int64)
    size = np.zeros(NQ, np.int64)
    rr = np.zeros(C, np.int64)
    busy = np.zeros(C, np.int64)
    stall = np.zeros(NQ, np.int64)
    wstall = np.zeros(R, np.int64)
    stalled_at = np.full(R, -1, np.int64)
    (offered, accepted, tagged, consumed_meas, consumed, injected,
     escaped) = (np.zeros(R, np.int64) for _ in range(7))

    rowsq = np.arange(NQ)
    rowsc = np.arange(C)
    srcs = np.tile(np.arange(n), R)
    lane_q = (np.arange(N) // n) * (n_ch * n_vc)
    lane_base = (rowsq // (n_ch * n_vc)) * (n_ch * n_vc)
    thresh = lo(lo(rates[:, None]) * lo(src_rate[None, :])).reshape(N)
    dg = deg[srcs]
    fp = lo(fprob)
    node_q = np.tile(ch_dst, R)[rowsq // n_vc]
    vc_q = rowsq % n_vc
    my_ch = (rowsq // n_vc) % n_ch
    u_all = uniforms(seed, cycles, N)

    i = 0
    while i < cycles and not (wstall >= watchdog).all():
        ph = int(i >= t_fault) if faulted else 0
        hw = q[rowsq, head]
        hf = hw & FLOW_MASK
        hh = (hw >> HOP_SHIFT) & HOP_MASK
        nonempty = size > 0
        if adaptive:
            dq = t.dst[hf]
            consume_q = nonempty & (node_q == dq)
            cand = np.clip(outch[node_q], 0, n_ch - 1)
            ok = ((minmask[ph, node_q, dq][:, None]
                   >> np.arange(D)[None, :]) & 1) > 0
            if faulted:
                ok &= alive[ph, cand]
            bv = 1 + dq % (n_vc - 1)
            occ = size[lane_base[:, None] + cand * n_vc + bv[:, None]]
            score = np.where(ok, slots - occ, -1)
            rot = (np.arange(D)[None, :] + rowsq[:, None] + i) % D
            j = np.argmax(score * D + rot, axis=1)
            best_ch = cand[rowsq, j]
            best_score = score[rowsq, j]
            on_path = (hh <= lenm1[hf]) \
                & (pvf[np.minimum(hptr[hf] + hh, H - 1)] // n_vc == my_ch)
            chan_s = pvf[np.minimum(hptr[hf] + hh + 1, H - 1)] // n_vc
            prim_occ = size[lane_base + chan_s * n_vc + bv]
            prim_take = on_path & ~consume_q & (prim_occ < slots) \
                & (prim_occ <= slots - best_score + 4)
            if faulted:
                prim_take &= alive[ph, chan_s]
            use_esc = (vc_q == 0) | (stall >= patience) \
                | ((best_score < 0) & ~prim_take)
            nxt_ch = np.where(use_esc, esc[ph, node_q, dq],
                              np.where(prim_take, chan_s, best_ch))
            nxt_vc = np.where(use_esc, 0, bv)
            valid = nxt_ch >= 0
            if faulted:
                valid &= alive[ph, np.clip(nxt_ch, 0, n_ch - 1)]
            tq = np.where(consume_q | ~valid, -1,
                          lane_base + np.clip(nxt_ch, 0, n_ch - 1) * n_vc
                          + nxt_vc)
            fwd_ok = nonempty & ~consume_q & (tq >= 0) \
                & (size[np.clip(tq, 0, NQ - 1)] < slots)
        else:
            consume_q = nonempty & (hh == lenm1[hf])
            nxt = pvf[np.minimum(hptr[hf] + hh + 1, H - 1)]
            tq = np.where(consume_q, -1, lane_base + nxt)
            if faulted:
                tq = np.where(alive[ph, nxt // n_vc], tq, -1)
            fwd_ok = nonempty & ~consume_q & (tq >= 0) \
                & (size[np.clip(tq, 0, NQ - 1)] < slots)

        # one VC per free channel, round robin from rr
        elig = ((consume_q | fwd_ok) & np.repeat(busy == 0, n_vc)) \
            .reshape(C, n_vc)
        offs = (rr[:, None] + np.arange(n_vc)[None, :]) % n_vc
        pri = np.take_along_axis(elig, offs, axis=1)
        any_e = pri.any(axis=1)
        win_v = (rr + np.argmax(pri, axis=1)) % n_vc
        win_q = rowsc * n_vc + win_v
        rr = np.where(any_e, (win_v + 1) % n_vc, rr)
        w_word = hw[win_q]
        w_consume = consume_q[win_q] & any_e
        w_target = np.where(any_e & ~w_consume, tq[win_q], -1)
        # a queue accepts one forward per cycle: lowest channel id wins
        wants = any_e & ~w_consume & (w_target >= 0)
        tgt = np.clip(w_target, 0, NQ - 1)
        first = np.full(NQ + 1, C, np.int64)
        np.minimum.at(first, np.where(wants, tgt, NQ), rowsc)
        w_push = wants & (first[tgt] == rowsc)
        w_pop = w_consume | w_push
        busy = np.where(w_pop, flits - 1, np.maximum(busy - 1, 0))
        p_slot = (head[tgt] + size[tgt]) % slots
        step = 1 << HOP_SHIFT
        if adaptive:
            full_hop = ((w_word >> HOP_SHIFT) & HOP_MASK) >= HOP_MASK
            push_word = np.where(full_hop, w_word, w_word + step)
        else:
            push_word = w_word + step

        # injection
        measure = i >= warmup
        u0, u1, u2 = u_all[i]
        want = lo(u0) < thresh
        pick = np.minimum(lo(lo(u1) * lo(dg)).astype(np.int64), dg - 1)
        f0 = np.minimum(src_ptr[srcs] + np.maximum(pick, 0), H_F - 1)
        fid = np.where(lo(u2) < fp[f0], f0, falias[f0])
        cv0 = pvf[hptr[fid]]
        ok0 = np.ones(N, bool)
        if faulted:
            ok0 = alive[ph, cv0 // n_vc]
        if adaptive:
            dstf = t.dst[fid]
            e0 = esc[ph, srcs, dstf]
            cv0 = np.where(ok0, (cv0 // n_vc) * n_vc + 1 + dstf % (n_vc - 1),
                           np.maximum(e0, 0) * n_vc)
            ok0 = ok0 | (e0 >= 0)
        iq = lane_q + cv0
        i_pop = w_pop[iq // n_vc] & (win_q[iq // n_vc] == iq)
        i_push = first[iq] < C
        inj = want & (size[iq] - i_pop + i_push < slots) & (dg > 0) & ok0
        i_slot = (head[iq] + size[iq] + i_push) % slots
        inj_word = fid | ((measure & inj).astype(np.int64) << TAG_SHIFT)

        rows = np.concatenate([np.where(w_push, tgt, NQ),
                               np.where(inj, iq, NQ)])
        keep = rows < NQ
        q[rows[keep], np.concatenate([p_slot, i_slot])[keep]] = \
            np.concatenate([push_word, inj_word])[keep]
        popq = np.where(w_pop, win_q, NQ)
        delta = np.zeros(NQ + 1, np.int64)
        np.add.at(delta, popq, -1)
        np.add.at(delta, rows, 1)
        size = size + delta[:NQ]
        bump = np.zeros(NQ + 1, np.int64)
        np.add.at(bump, popq, 1)
        head = (head + bump[:NQ]) % slots

        meas = 1 if measure else 0
        cons_lane = w_consume.reshape(R, n_ch).sum(axis=1)
        inj_lane = inj.reshape(R, n).sum(axis=1)
        offered += meas * want.reshape(R, n).sum(axis=1)
        accepted += meas * inj_lane
        tagged += (w_consume & (((w_word >> TAG_SHIFT) & 1) == 1)) \
            .reshape(R, n_ch).sum(axis=1)
        consumed_meas += meas * cons_lane
        consumed += cons_lane
        injected += inj_lane
        if adaptive:
            popped = w_pop[rowsq // n_vc] & (win_q[rowsq // n_vc] == rowsq)
            stall = np.where(nonempty & ~popped, stall + 1, 0)
            escaped += (w_push & (tgt % n_vc == 0) & (win_q % n_vc != 0)) \
                .reshape(R, n_ch).sum(axis=1)
        progress = (w_pop.reshape(R, n_ch).sum(axis=1) > 0) | (inj_lane > 0)
        wstall = np.where((injected - consumed > 0) & ~progress,
                          wstall + 1, 0)
        stalled_at = np.where((wstall >= watchdog) & (stalled_at < 0), i,
                              stalled_at)
        i += 1

    meas_c = cycles - warmup
    in_flight = size.reshape(R, -1).sum(axis=1)
    lanes = [{
        "rate": float(rates[k]),
        "offered": float(offered[k]) / meas_c / n,
        "accepted": float(accepted[k]) / meas_c / n,
        "delivered": float(consumed_meas[k]) / meas_c / n,
        "delivered_tagged": float(tagged[k]) / meas_c / n,
        "consumed_total": int(consumed[k]),
        "injected_total": int(injected[k]),
        "in_flight": int(in_flight[k]),
        "escaped": int(escaped[k]),
        "stalled_at": int(stalled_at[k]),
    } for k in range(R)]
    return lanes, i


def mismatches(out: dict, lanes: list, cycles_run: int) -> int:
    """Counters of a sweep's output (``lanes`` and ``cycles_run`` as the
    program reports them) that differ from the reference's."""
    bad = int(out["cycles_run"] != cycles_run) \
        + abs(len(out["lanes"]) - len(lanes))
    for a, b in zip(out["lanes"], lanes):
        bad += sum(a.get(k) != v for k, v in b.items())
    return bad
