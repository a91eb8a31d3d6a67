"""Plain reference for the simulator's trace replay: a cyclic schedule
of demand phases.

It imports nothing of the program under test. It is
``netsim_ref.simulate`` with one change, which a phased sweep must
reproduce bit for bit: each phase has its own demand and source rates
(its own Vose alias tables over the routed flows and its own injection
thresholds), and cycle ``i`` injects with the phase that
``i mod period`` falls in, the phases running in order for their
cycles. The random draws per cycle are those of a stationary sweep.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from bench.reference.fabric import Fabric, Table
from bench.reference.netsim_ref import (FLOW_MASK, HOP_MASK, HOP_SHIFT,
                                        TAG_SHIFT, adaptive_tables,
                                        flow_alias, uniforms)


def simulate(fab: Fabric, t: Table, phases: Sequence[tuple],
             phase_cycles: Sequence[int], rates: Sequence[float],
             seed: int, *, cycles: int, warmup: int, slots: int,
             flits: int, adaptive: bool = False,
             fault: Optional[tuple] = None, patience: int = 64,
             watchdog: int = 512, dtype: str = "float32"):
    """Every rate lane's counters, as a list of dicts (the sweep's own
    layout), and the number of cycles run. ``phases`` holds each
    phase's (n, n) float64 demand and (n,) float32 source rates; phase
    ``p`` lasts ``phase_cycles[p]`` cycles of the cyclic schedule."""
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
    alias = [flow_alias(m, t) for m, _ in phases]
    fprob = np.stack([a[0] for a in alias])
    falias = np.stack([a[1] for a in alias])
    src_rate = np.stack([np.asarray(r, np.float32) for _, r in phases])
    phase_of = np.repeat(np.arange(len(phases)),
                         np.asarray(phase_cycles, np.int64))
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
    # per phase: (P, N) thresholds
    thresh = lo(lo(rates[None, :, None]) * lo(src_rate[:, None, :])) \
        .reshape(len(phases), N)
    dg = deg[srcs]
    fp = lo(fprob)
    node_q = np.tile(ch_dst, R)[rowsq // n_vc]
    vc_q = rowsq % n_vc
    my_ch = (rowsq // n_vc) % n_ch
    u_all = uniforms(seed, cycles, N)

    i = 0
    while i < cycles and not (wstall >= watchdog).all():
        ph = int(i >= t_fault) if faulted else 0
        pz = phase_of[i % len(phase_of)]
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
        want = lo(u0) < thresh[pz]
        pick = np.minimum(lo(lo(u1) * lo(dg)).astype(np.int64), dg - 1)
        f0 = np.minimum(src_ptr[srcs] + np.maximum(pick, 0), H_F - 1)
        fid = np.where(lo(u2) < fp[pz][f0], f0, falias[pz][f0])
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
