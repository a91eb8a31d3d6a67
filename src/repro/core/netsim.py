"""Cycle-level network simulator, vectorised and jitted in JAX.

Replaces CNSim (paper Section 6.1) for this container: synchronous
packet-granularity wormhole approximation with per-(channel, VC) FIFOs,
round-robin VC arbitration, one packet serviced per channel per cycle and
one packet accepted per queue per cycle (crossbar constraint; losers
stall and retry), static single-path routing tables and per-hop VC
assignments from the AT pipeline.

The default kernel is *CSR-native* (``kernel="csr"``): packet words carry
a routed-flow id, and next-channel/next-VC lookups gather from the
``CSRPathTable``'s concatenated hop array via ``hop_indptr[flow] + hop``
indexing. Peak simulator memory therefore scales with total routed hops
(O(H), ~73 MB at 12^3) instead of the dense ``(n, n, MAXHOP)`` gather
tables (O(n^2 * MAXHOP), ~480 MB at 12^3 and ~3.4 GB at 16^3, which also
exceeds the dense packet word's 12-bit node fields). The legacy dense
kernel survives as ``kernel="dense"``: it consumes the same flow-slot
traffic tables and the same RNG stream, so its per-rate counters are
bit-identical to the CSR kernel's -- the equivalence oracle exercised by
``tests/test_netsim_csr.py``. Keep the two cycle bodies in lockstep.

Traffic is pluggable (:class:`repro.core.traffic.TrafficPattern`): demand
matrices compile onto the table's flow slots
(:class:`repro.core.traffic.CompiledFlowTraffic`, O(F) alias tables), so
uniform-random, permutation, hotspot and demand-driven patterns all share
one compiled simulator. Demand on unrouted pairs is dropped at compile
time (rows renormalise over routed flows). Injection-rate sweeps run all
rates in one batched device execution (lane-flattened rather than
``jax.vmap``-ed -- see :func:`_sweep_csr`) instead of a Python loop of
per-rate jit calls.

Accounting: ``delivered`` is the measurement-window consumption rate (the
steady-state throughput estimator -- arrivals of warmup-injected packets
cancel the still-in-flight tail). Packets injected during the window are
additionally tagged, and ``delivered_tagged`` counts only those arrivals,
so ``delivered_tagged <= accepted <= offered`` holds exactly;
``injected_total`` / ``consumed_total`` / ``in_flight`` (whole run)
satisfy packet conservation ``injected == consumed + in_flight``.
Saturation = largest rate whose delivered throughput tracks the offered
rate (CNSim's first-timeout criterion, in deficit form).

Defaults follow Table 2 where representable at packet granularity
(radix 6, 2 escape VCs of the 4 total, buffering in packet slots).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import (Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.pathtable import MAXHOP, CSRPathTable, PathTable
from repro.core.routing import (ATResult, Channels, RoutingResult,
                                _dead_channel_array)
from repro.core.topology import Topology
from repro.core.traffic import (CompiledFlowTraffic, CompiledTraffic,
                                PhasedTraffic, TenantMap, TrafficPattern,
                                compile_flow_traffic)


@dataclasses.dataclass
class SimTables:
    """Static routing tables for the simulator.

    Accepts either path-table layout and keeps it as-is in ``table``;
    the CSR form is what the default simulator kernel consumes directly,
    so a 12^3/16^3 route-and-simulate pipeline never materialises the
    ``n^2 * MAXHOP`` arrays. Conversions are cached on the side:
    :meth:`csr` packs a dense table once, :meth:`dense` (and the
    ``path``/``vcs``/``hops`` views, kept for the dense kernel and
    API-edge consumers) densifies a CSR table once.
    """
    n: int
    n_ch: int
    n_vc: int
    ch_dst: np.ndarray                  # (C,)
    table: Union[PathTable, CSRPathTable]
    _dense_cache: Optional[PathTable] = \
        dataclasses.field(default=None, repr=False)
    _csr_cache: Optional[CSRPathTable] = \
        dataclasses.field(default=None, repr=False)

    def dense(self) -> PathTable:
        if isinstance(self.table, PathTable):
            return self.table
        if self._dense_cache is None:
            self._dense_cache = self.table.to_dense()
        return self._dense_cache

    def csr(self) -> CSRPathTable:
        if isinstance(self.table, CSRPathTable):
            return self.table
        if self._csr_cache is None:
            self._csr_cache = CSRPathTable.from_dense(self.table)
        return self._csr_cache

    @property
    def path(self) -> np.ndarray:
        return self.dense().path

    @property
    def vcs(self) -> np.ndarray:
        return self.dense().vcs

    @property
    def hops(self) -> np.ndarray:
        return self.dense().hops


def build_tables(topo: Topology,
                 table: Union[PathTable, CSRPathTable, RoutingResult]
                 ) -> SimTables:
    """Packed path table (or a RoutingResult carrying one) -> SimTables.

    No per-pair python loops: the table arrives already packed from path
    selection / DOR construction / VC allocation, in either the dense or
    the CSR layout.
    """
    if isinstance(table, RoutingResult):
        table = table.table
    ch = Channels.from_topology(topo)
    if table.n_ch != ch.n:
        raise ValueError(f"table built for {table.n_ch} channels, "
                         f"topology has {ch.n}")
    return SimTables(table.n, ch.n, table.n_vc, ch.dst.astype(np.int32),
                     table)


# ---------------------------------------------------------------------------
# Jitted kernels: all injection rates batched as lane-flattened simulations
# ---------------------------------------------------------------------------


# Packet word layouts (one int32 per packet; packing all attributes into
# one word turns the four per-attribute scatter updates of the seed
# kernel into a single scatter -- scatters serialise on CPU and dominated
# the vmapped sweep's wall-clock):
#
#   dense kernel:  src[0:12] | dst[12:24] | hop[24:30] | tag[30]
#                  (n <= 4095 -- the dense kernel cannot pack 16^3)
#   csr kernel:    flow[0:24] | hop[24:30] | tag[30]
#                  (F <= 2^24 - 1; all-pairs 16^3 = 4096*4095 still fits)
#
# MAXHOP <= 63 for both; checked in `sweep`.
_SRC_BITS = 12
_DST_SHIFT = 12
_HOP_SHIFT = 24
_TAG_SHIFT = 30
_FIELD_MASK = (1 << 12) - 1
_HOP_MASK = (1 << 6) - 1
_FLOW_MASK = (1 << 24) - 1


def _pack(src, dst, hop, tag):
    return (src | (dst << _DST_SHIFT) | (hop << _HOP_SHIFT)
            | (tag.astype(jnp.int32) << _TAG_SHIFT))


def _pack_flow(flow, hop, tag):
    return (flow | (hop << _HOP_SHIFT)
            | (tag.astype(jnp.int32) << _TAG_SHIFT))


@partial(jax.jit, static_argnames=("R", "n", "n_ch", "n_vc", "slots",
                                   "cycles", "warmup", "flits", "adaptive",
                                   "faulted", "bursty", "patience",
                                   "watchdog", "D", "period", "on_cycles",
                                   "T", "phased", "p_period"))
def _sweep_csr(ch_dst, pvf, hptr, lenm1, dstN, src_ptr, deg, fprob, falias,
               src_rate, rates, key, outch, minmask, esc, alive, t_fault,
               g_on, g_off, phase, tof, tmap, phase_of, *, R, n, n_ch, n_vc,
               slots, cycles, warmup, flits, adaptive=False, faulted=False,
               bursty=False, patience=64, watchdog=512, D=1, period=0,
               on_cycles=0, T=0, phased=False, p_period=1):
    """R independent simulations (one per injection rate) in one compiled
    execution, gathering routes from the CSR hop arrays.

    The batch is *lane-flattened* rather than ``jax.vmap``-ed: lane ``l``'s
    queue (c, v) lives at flat row ``l*NQ + c*n_vc + v``, so every update
    in the cycle body stays an ordinary rank-1 gather/scatter. (A vmapped
    version was measured first: XLA CPU lowers batched scatter/sort so
    poorly that it ran slower than the sequential python loop. Because the
    flat queue id factors as ``fc * n_vc + v`` with ``fc = l*C + c``, the
    single-lane arbitration/rank formulas carry over verbatim.)

    On a TPU a gather or scatter costs about the same per index whatever
    the size of its table, so the cycle body gathers and scatters only at
    indices the data decides. What the queue layout or the loop fixes is
    dense: each ring's head slot is a select over the slots axis; a
    channel's winning VC is a one-hot over its ``n_vc`` queues, which also
    reads the winner's word, target and consume flag and applies the pops
    to ``head`` and ``size``; the adaptive candidates and their liveness
    are computed before the loop, and their occupancy is read once per
    (lane, node, candidate). ``tests/test_netsim_gathers.py`` keeps the
    count of gathered and scattered indices per cycle.

    Route lookups are flow-native: a head word's next (channel, VC) is
    ``pvf[hptr[flow] + hop + 1]`` and it consumes when ``hop`` reaches
    ``lenm1[flow]`` -- no (n, n, MAXHOP) arrays anywhere. ``pvf`` packs
    ``channel * n_vc + vc`` per hop (one gather serves both fields).

    Extension flags (all python-static, so the default trace -- and its
    counters -- is bit-identical to the plain static kernel):

    - ``adaptive``: heads on VCs >= 1 pick among the minimal alternates
      of ``outch``/``minmask`` by downstream adaptive-VC free space and
      divert to the escape lane (VC 0, routed by ``esc``) after
      ``patience`` stalled cycles or when no live alternate exists;
      VC 0 heads always follow the escape tree. ``dstN`` maps flow ->
      destination node (consumption becomes node-arrival, not
      hop-count).
    - ``faulted``: channels with ``alive[1, c] == 0`` stop accepting
      forwards/injections from cycle ``t_fault`` on (their queues still
      drain -- the buffer sits at the receiving node); tables indexed
      ``[ph]`` switch from the pre- to the post-fault plane.
    - ``bursty``: injection thresholds are modulated by the
      mean-preserving on/off gains (``g_on``/``g_off``) on a
      ``period``-cycle schedule offset per source by ``phase``.
    - watchdog (always on): a lane with packets in flight that neither
      pops nor injects for ``watchdog`` consecutive cycles is marked
      stalled (``stalled_at`` = cycle of detection); when *every* lane
      is stalled the sweep aborts early instead of spinning out the
      budget.
    - ``phased`` (trace replay): ``fprob``/``falias``/``src_rate`` carry
      a leading phase axis and ``phase_of[i % p_period]`` selects the
      active demand phase each cycle -- same RNG draw count as the
      stationary path, so a single-phase schedule is bit-identical.
    - ``T > 0`` (multi-tenant): ``tof`` maps flow -> tenant id (-1 =
      none) and the kernel keeps per-(lane, tenant) injected / consumed
      / consumed-in-window counters plus end-of-run queued words, giving
      exact per-tenant conservation (injected == consumed + in-flight).
      No extra RNG draws, so the default trace is unchanged when off.
    """
    C = R * n_ch                    # flat channels across lanes
    NQ = C * n_vc                   # flat queues across lanes
    N = R * n                       # flat sources across lanes
    H = pvf.shape[0]

    # queue state: per-(lane, channel, vc) ring buffers of packed words
    q = jnp.zeros((NQ, slots), jnp.int32)
    head = jnp.zeros((NQ,), jnp.int32)
    size = jnp.zeros((NQ,), jnp.int32)
    rr = jnp.zeros((C,), jnp.int32)
    busy = jnp.zeros((C,), jnp.int32)   # flit-serialisation countdown

    srcs = jnp.tile(jnp.arange(n), R)            # local node ids per lane
    lane_q = (jnp.arange(N) // n) * (n_ch * n_vc)
    dg = deg[srcs]                               # flows per source
    ptr0 = src_ptr[srcs]                         # first flow slot per source
    vcs = jnp.arange(n_vc)
    slot_ids = jnp.arange(slots)
    if T:
        word_tenant = lambda w: tof[w & _FLOW_MASK]   # noqa: E731
    if not phased:
        thresh = (rates[:, None] * src_rate[None, :]).reshape(N)
    if bursty:
        phs = jnp.tile(phase, R)                 # (N,) per-source offsets
    if adaptive:
        node_q = jnp.tile(ch_dst, R)[jnp.arange(NQ) // n_vc]
        vc_q = jnp.arange(NQ) % n_vc
        qrows = jnp.arange(NQ)
        out_ch = jnp.clip(outch, 0, n_ch - 1)                  # (n, D)
        cand_ch = out_ch[node_q]                                # (NQ, D)
        # the candidates' queues are shared by every queue at a node: a
        # cycle reads them once per (lane, node, candidate) as rows of
        # n_vc, then each queue takes the rows of its own (lane, node)
        cand_row = (jnp.arange(R)[:, None, None] * n_ch
                    + out_ch[None]).reshape(-1)
        node_row = (qrows // (n_ch * n_vc)) * n + node_q        # (NQ,)
        if faulted:
            # candidate liveness on both planes; a cycle selects its plane
            cand_alive = alive[:, cand_ch] > 0                 # (2, NQ, D)

    def cycle(carry):
        i, q, head, size, rr, busy, key, stall, wstall, stalled_at, \
            stats = carry
        (offered, accepted, tagged, consumed_meas, consumed, injected,
         escaped, hops, inj_t, cons_t, consm_t) = stats
        ph = (i >= t_fault).astype(jnp.int32) if faulted else 0
        phz = phase_of[i % p_period] if phased else 0

        with jax.named_scope("route"):
            # ---- head packet per (lane, channel, vc) ------------------------
            # a dense select of each ring's head slot, not a gather
            hw = jnp.sum(jnp.where(slot_ids[None, :] == head[:, None], q, 0),
                         axis=1)
            hf = hw & _FLOW_MASK
            hh = (hw >> _HOP_SHIFT) & _HOP_MASK
            nonempty = size > 0

            lane_base = (jnp.arange(NQ) // (n_ch * n_vc)) * (n_ch * n_vc)
            if adaptive:
                # consume on destination arrival; next hop chosen live among
                # the minimal alternates by downstream adaptive free space,
                # escape lane (VC0 over the tree) as the safe fallback
                dq = dstN[hf]
                consume_q = nonempty & (node_q == dq)
                mm = minmask[ph, node_q, dq]
                ok_cand = ((mm[:, None] >> jnp.arange(D)[None, :]) & 1) > 0
                if faulted:
                    ok_cand = ok_cand & jnp.where(ph > 0, cand_alive[1],
                                                  cand_alive[0])
                # free space of the queue the packet would actually join:
                # its destination-bound adaptive VC on each candidate channel
                vq = 1 + dq % (n_vc - 1)
                rows = size.reshape(C, n_vc)[cand_row].reshape(R * n, D,
                                                                n_vc)
                occ = jnp.sum(jnp.where(vcs == vq[:, None, None],
                                        rows[node_row], 0), axis=2)
                score = jnp.where(ok_cand, slots - occ, -1)
                # rotate tie-breaks per (queue, cycle): equal scores would
                # otherwise herd every packet at a node onto one alternate
                rot = (jnp.arange(D)[None, :] + qrows[:, None] + i) % D
                # rot is a permutation of 0..D-1 per row, so the keys of a
                # row differ and the largest picks one candidate; as
                # 0 <= rot < D, its floor over D is that candidate's score
                key_d = score * D + rot
                top = jnp.max(key_d, axis=1)
                best_ch = jnp.sum(jnp.where(key_d == top[:, None], cand_ch, 0),
                                  axis=1)
                best_score = top // D
                has_cand = best_score >= 0
                # destination-bound adaptive VC: confines any one endpoint's
                # backlog to a single VC per channel, so victim flows keep
                # the other adaptive VCs (least-occupied selection was
                # measured to level-fill every VC with hotspot backlog and
                # collapse total throughput well below the static tables)
                bv = 1 + dq % (n_vc - 1)
                # planned-path-first: a packet still on its static path keeps
                # it while the destination-bound queue ahead has room -- the
                # LP-balanced tables confine backlog to the same narrow cones
                # static routing does -- and only overflows onto the freest
                # minimal alternate (off-path and post-fault packets route
                # fully adaptively)
                my_ch = (qrows // n_vc) % n_ch
                on_path = (hh <= lenm1[hf]) \
                    & (pvf[jnp.minimum(hptr[hf] + hh, H - 1)] // n_vc
                       == my_ch)
                chan_s = pvf[jnp.minimum(hptr[hf] + hh + 1, H - 1)] // n_vc
                prim_occ = size[lane_base + chan_s * n_vc + bv]
                best_occ = slots - best_score         # slots + 1 when no cand
                prim_take = on_path & ~consume_q & (prim_occ < slots) \
                    & (prim_occ <= best_occ + 4)
                if faulted:
                    prim_take = prim_take & (alive[ph, chan_s] > 0)
                use_esc = (vc_q == 0) | (stall >= patience) \
                    | (~has_cand & ~prim_take)
                e_ch = esc[ph, node_q, dq]
                nxt_ch = jnp.where(use_esc, e_ch,
                                   jnp.where(prim_take, chan_s, best_ch))
                nxt_vc = jnp.where(use_esc, 0, bv)
                valid = nxt_ch >= 0
                if faulted:
                    valid = valid & (alive[ph, jnp.clip(nxt_ch, 0,
                                                        n_ch - 1)] > 0)
                tq = jnp.where(consume_q | ~valid, -1,
                               lane_base
                               + jnp.clip(nxt_ch, 0, n_ch - 1) * n_vc
                               + nxt_vc)
                fwd_ok = nonempty & ~consume_q & (tq >= 0) \
                    & (size[jnp.clip(tq, 0, NQ - 1)] < slots)
            else:
                consume_q = nonempty & (hh == lenm1[hf])
                nxt = pvf[jnp.minimum(hptr[hf] + hh + 1, H - 1)]
                tq = jnp.where(consume_q, -1, lane_base + nxt)
                if faulted:
                    # dead next hop: the packet waits in place (and the
                    # watchdog eventually reports the wedged lane)
                    tq = jnp.where(alive[ph, nxt // n_vc] > 0, tq, -1)
                    fwd_ok = nonempty & ~consume_q & (tq >= 0) \
                        & (size[jnp.clip(tq, 0, NQ - 1)] < slots)
                else:
                    fwd_ok = nonempty & ~consume_q \
                        & (size[jnp.clip(tq, 0, NQ - 1)] < slots)
            eligible = consume_q | fwd_ok                   # per (c, v)

        with jax.named_scope("arbitrate"):
            # ---- round-robin arbitration: one vc per channel ----------------
            # multi-flit packets occupy the link for `flits` cycles
            eligible = eligible & jnp.repeat(busy == 0, n_vc)
            # the winner is the eligible vc nearest past the pointer rr
            # (rr itself first); with none eligible win_v stays rr
            gap = jnp.where(eligible.reshape(C, n_vc),
                            (vcs[None, :] - rr[:, None]) % n_vc, n_vc)
            nearest = jnp.min(gap, axis=1)
            win_valid = nearest < n_vc
            win_v = (rr + nearest) % n_vc
            win_oh = vcs[None, :] == win_v[:, None]          # (C, n_vc)
            rr = jnp.where(win_valid, (win_v + 1) % n_vc, rr)

            def at_win(x):      # x per queue -> x of each channel's winner
                return jnp.sum(jnp.where(win_oh, x.reshape(C, n_vc), 0),
                               axis=1)

            w_word = at_win(hw)
            w_tag = (w_word >> _TAG_SHIFT) & 1
            w_consume = jnp.any(win_oh & consume_q.reshape(C, n_vc), axis=1) \
                & win_valid
            w_target = jnp.where(win_valid & ~w_consume, at_win(tq), -1)

        with jax.named_scope("crossbar"):
            # ---- crossbar constraint: one push per target queue per cycle ---
            # (a router output accepts one packet from the crossbar per cycle;
            # the lowest-id input wins, losers stall and retry next cycle).
            # Targets never collide across lanes: flat queue ids are disjoint.
            cand = win_valid & ~w_consume & (w_target >= 0)
            tgt = jnp.clip(w_target, 0, NQ - 1)
            first = jnp.full((NQ + 1,), C, jnp.int32) \
                .at[jnp.where(cand, tgt, NQ)] \
                .min(jnp.arange(C, dtype=jnp.int32))
            w_push = cand & (first[tgt] == jnp.arange(C))
            w_pop = w_consume | w_push
            pop_q = (win_oh & w_pop[:, None]).reshape(NQ)   # popped queues
            busy = jnp.where(w_pop, flits - 1, jnp.maximum(busy - 1, 0))

        with jax.named_scope("push"):
            # ---- push slots -------------------------------------------------
            # post-pop (head + size) equals pre-pop (head + size): a pop moves
            # head forward and shrinks size by one, so the tail slot is stable
            p_slot = (head[tgt] + size[tgt]) % slots
            if adaptive:
                # adaptive paths are not length-bounded by the route table, so
                # saturate the 6-bit hop field instead of wrapping into the tag
                w_hh = (w_word >> _HOP_SHIFT) & _HOP_MASK
                push_word = jnp.where(w_hh >= _HOP_MASK, w_word,
                                      w_word + (1 << _HOP_SHIFT))
            else:
                push_word = w_word + (1 << _HOP_SHIFT)  # hop += 1, rest intact

        with jax.named_scope("inject"):
            # ---- injection: alias-sampled routed flow per source ------------
            measure = i >= warmup
            key, k1, k2, k3 = jax.random.split(key, 4)
            if phased:
                thr = (rates[:, None] * src_rate[phz][None, :]).reshape(N)
                fp, fa = fprob[phz], falias[phz]
            else:
                thr, fp, fa = thresh, fprob, falias
            if bursty:
                on = ((i + phs) % period) < on_cycles
                want = jax.random.uniform(k1, (N,)) \
                    < thr * jnp.where(on, g_on, g_off)
            else:
                want = jax.random.uniform(k1, (N,)) < thr
            u1 = jax.random.uniform(k2, (N,))
            j = jnp.minimum((u1 * dg.astype(jnp.float32)).astype(jnp.int32),
                            dg - 1)
            f0 = ptr0 + jnp.maximum(j, 0)
            u2 = jax.random.uniform(k3, (N,))
            fid = jnp.where(u2 < fp[f0], f0, fa[f0])
            cv0 = pvf[hptr[fid]]
            if adaptive or faulted:
                ch0 = cv0 // n_vc
                ok0 = (alive[ph, ch0] > 0) if faulted \
                    else jnp.ones((N,), bool)
                if adaptive:
                    # the stored VC is a static-mode artifact: inject onto
                    # the planned channel's destination-bound adaptive VC
                    # (sources can always wait, so injection never needs the
                    # escape guarantee). Planned first hop dead: inject
                    # straight onto the escape tree; no escape route -> hold.
                    dstf = dstN[fid]
                    iv = 1 + dstf % (n_vc - 1)
                    e0 = esc[ph, srcs, dstf]
                    cv0 = jnp.where(ok0, ch0 * n_vc + iv,
                                    jnp.maximum(e0, 0) * n_vc)
                    ok0 = ok0 | (e0 >= 0)
                iq = lane_q + cv0
            else:
                iq = lane_q + cv0
            i_pop = pop_q[iq].astype(jnp.int32)
            # at most one push lands in iq this cycle (crossbar constraint)
            i_push = (first[iq] < C).astype(jnp.int32)
            i_size = size[iq]
            has_space = i_size - i_pop + i_push < slots
            inj = want & has_space & (dg > 0)
            if adaptive or faulted:
                inj = inj & ok0
            i_slot = (head[iq] + i_size + i_push) % slots
            inj_word = _pack_flow(fid, jnp.zeros((N,), jnp.int32),
                                  measure & inj)

        with jax.named_scope("scatter"):
            # ---- one fused scatter for pushes + injections ------------------
            all_rows = jnp.concatenate([jnp.where(w_push, tgt, NQ),
                                        jnp.where(inj, iq, NQ)])
            all_slots = jnp.concatenate([p_slot, i_slot])
            all_words = jnp.concatenate([push_word, inj_word])
            q = q.at[all_rows, all_slots].set(all_words, mode="drop")

            # ---- pops are dense; one scatter-add for pushes + injections ---
            pop_i = pop_q.astype(jnp.int32)
            size = (size - pop_i).at[all_rows].add(1, mode="drop")
            head = (head + pop_i) % slots

        with jax.named_scope("counters"):
            meas = jnp.where(measure, 1, 0)
            cons_lane = w_consume.reshape(R, n_ch).sum(axis=1)
            inj_lane = inj.reshape(R, n).sum(axis=1)
            offered = offered + meas * want.reshape(R, n).sum(axis=1)
            accepted = accepted + meas * inj_lane
            tagged = tagged + (w_consume & (w_tag == 1)).reshape(
                R, n_ch).sum(axis=1)
            consumed_meas = consumed_meas + meas * cons_lane
            consumed = consumed + cons_lane
            injected = injected + inj_lane

            if T:
                # per-(lane, tenant) accounting; flow -> tenant is static
                # (`tof`), so attribution costs two gathers and two
                # scatter-adds, no extra RNG
                t_w = tof[w_word & _FLOW_MASK]
                ok_w = w_consume & (t_w >= 0)
                rowc = (jnp.arange(C) // n_ch) * T + jnp.clip(t_w, 0, T - 1)
                cons_t = cons_t.at[rowc].add(ok_w.astype(jnp.int32))
                consm_t = consm_t.at[rowc].add(
                    (ok_w & measure).astype(jnp.int32))
                t_i = tof[fid]
                rowi = (jnp.arange(N) // n) * T + jnp.clip(t_i, 0, T - 1)
                inj_t = inj_t.at[rowi].add(
                    (inj & (t_i >= 0)).astype(jnp.int32))

            if adaptive:
                # per-queue persistent-stall counter (drives escape diversion)
                stall = jnp.where(nonempty & ~pop_q, stall + 1, 0)
                # escape diversions: pushes that land on VC0 from a VC >= 1
                escaped = escaped + (w_push & (tgt % n_vc == 0)
                                     & (win_v != 0)).reshape(
                    R, n_ch).sum(axis=1)

        with jax.named_scope("watchdog"):
            # ---- watchdog: lanes with traffic but zero forward progress -----
            pop_lane = w_pop.reshape(R, n_ch).sum(axis=1)
            hops = hops + pop_lane          # packet-hops: pops of any queue
            progress = (pop_lane > 0) | (inj_lane > 0)
            wstall = jnp.where((injected - consumed > 0) & ~progress,
                               wstall + 1, 0)
            stalled_at = jnp.where((wstall >= watchdog) & (stalled_at < 0),
                                   i, stalled_at)
        return (i + 1, q, head, size, rr, busy, key, stall, wstall,
                stalled_at,
                (offered, accepted, tagged, consumed_meas, consumed,
                 injected, escaped, hops, inj_t, cons_t, consm_t))

    stats0 = (jnp.zeros((R,), jnp.int32),) * 8 \
        + (jnp.zeros((R * T,), jnp.int32),) * 3
    stall0 = jnp.zeros((NQ if adaptive else 1,), jnp.int32)
    carry = (jnp.int32(0), q, head, size, rr, busy, key, stall0,
             jnp.zeros((R,), jnp.int32), jnp.full((R,), -1, jnp.int32),
             stats0)

    def cond(carry):
        return (carry[0] < cycles) & ~jnp.all(carry[8] >= watchdog)

    carry = jax.lax.while_loop(cond, cycle, carry)
    q, head, size = carry[1], carry[2], carry[3]
    stalled_at = carry[9]
    (offered, accepted, tagged, consumed_meas, consumed, injected,
     escaped, hops, inj_t, cons_t, consm_t) = carry[-1]
    if T:
        # per-tenant end-of-run occupancy from the final ring buffers:
        # slot j of queue r holds a live word iff (j - head) % slots
        # < size -- exact, so injected == consumed + in_flight per tenant
        occ = ((jnp.arange(slots)[None, :] - head[:, None]) % slots) \
            < size[:, None]
        tw = word_tenant(q)                             # (NQ, slots)
        rows = (jnp.arange(NQ) // (n_ch * n_vc))[:, None] * T \
            + jnp.clip(tw, 0, T - 1)
        infl_t = jnp.zeros((R * T,), jnp.int32) \
            .at[rows].add((occ & (tw >= 0)).astype(jnp.int32))
    else:
        infl_t = jnp.zeros((0,), jnp.int32)
    return (offered, accepted, tagged, consumed_meas, consumed, injected,
            escaped, hops, size.reshape(R, -1).sum(axis=1), stalled_at,
            inj_t, cons_t, consm_t, infl_t, carry[0])


@partial(jax.jit, static_argnames=("R", "n", "n_ch", "n_vc", "slots",
                                   "cycles", "warmup", "flits", "adaptive",
                                   "faulted", "bursty", "patience",
                                   "watchdog", "D", "period", "on_cycles",
                                   "T", "phased", "p_period"))
def _sweep_dense(ch_dst, pv, fdst, src_ptr, deg, fprob, falias,
                 src_rate, rates, key, outch, minmask, esc, alive, t_fault,
                 g_on, g_off, phase, tof, tmap, phase_of, *, R, n, n_ch,
                 n_vc, slots, cycles, warmup, flits, adaptive=False,
                 faulted=False, bursty=False, patience=64, watchdog=512,
                 D=1, period=0, on_cycles=0, T=0, phased=False,
                 p_period=1):
    """Legacy dense-gather kernel: identical cycle body to
    :func:`_sweep_csr` (same RNG stream, same flow-slot sampling, same
    arbitration) except route lookups gather from the dense
    ``(n, n, MAXHOP)`` composite table and packet words carry (src, dst)
    node ids. Kept as the bit-identity oracle for the CSR kernel -- edit
    the two cycle bodies in lockstep. The adaptive/faulted/bursty flags
    and the always-on watchdog mirror :func:`_sweep_csr` exactly (the
    dense word already carries the destination, so no ``dstN`` gather is
    needed).
    """
    C = R * n_ch
    NQ = C * n_vc
    N = R * n

    q = jnp.zeros((NQ, slots), jnp.int32)
    head = jnp.zeros((NQ,), jnp.int32)
    size = jnp.zeros((NQ,), jnp.int32)
    rr = jnp.zeros((C,), jnp.int32)
    busy = jnp.zeros((C,), jnp.int32)

    arrive_node = jnp.tile(ch_dst, R)[jnp.arange(NQ) // n_vc]
    srcs = jnp.tile(jnp.arange(n), R)
    lane_q = (jnp.arange(N) // n) * (n_ch * n_vc)
    if T:
        word_tenant = lambda w: tmap[w & _FIELD_MASK,   # noqa: E731
                                     (w >> _DST_SHIFT) & _FIELD_MASK]
    if not phased:
        thresh = (rates[:, None] * src_rate[None, :]).reshape(N)
    if bursty:
        phs = jnp.tile(phase, R)
    if adaptive:
        vc_q = jnp.arange(NQ) % n_vc
        qrows = jnp.arange(NQ)

    def cycle(carry):
        i, q, head, size, rr, busy, key, stall, wstall, stalled_at, \
            stats = carry
        (offered, accepted, tagged, consumed_meas, consumed, injected,
         escaped, hops, inj_t, cons_t, consm_t) = stats
        ph = (i >= t_fault).astype(jnp.int32) if faulted else 0
        phz = phase_of[i % p_period] if phased else 0

        with jax.named_scope("route"):
            hw = q[jnp.arange(NQ), head]
            hs = hw & _FIELD_MASK
            hd = (hw >> _DST_SHIFT) & _FIELD_MASK
            hh = (hw >> _HOP_SHIFT) & _HOP_MASK
            nonempty = size > 0

            consume_q = nonempty & (arrive_node == hd)
            lane_base = (jnp.arange(NQ) // (n_ch * n_vc)) * (n_ch * n_vc)
            if adaptive:
                dq = hd
                cand_ch = jnp.clip(outch[arrive_node], 0, n_ch - 1)
                mm = minmask[ph, arrive_node, dq]
                ok_cand = ((mm[:, None] >> jnp.arange(D)[None, :]) & 1) > 0
                if faulted:
                    ok_cand = ok_cand & (alive[ph, cand_ch] > 0)
                # free space of the queue the packet would actually join:
                # its destination-bound adaptive VC on each candidate channel
                vq = (1 + dq % (n_vc - 1))[:, None]
                occ = size[lane_base[:, None] + cand_ch * n_vc + vq]
                score = jnp.where(ok_cand, slots - occ, -1)
                rot = (jnp.arange(D)[None, :] + qrows[:, None] + i) % D
                j = jnp.argmax(score * D + rot, axis=1)    # rotating tie-break
                best_ch = cand_ch[qrows, j]
                has_cand = score[qrows, j] >= 0
                bv = 1 + dq % (n_vc - 1)    # destination-bound VC (see CSR)
                # planned-path-first, mirroring the CSR kernel
                my_ch = (qrows // n_vc) % n_ch
                pcur = pv[hs, hd, hh]
                on_path = (pcur >= 0) & (pcur // n_vc == my_ch)
                pnxt = pv[hs, hd, hh + 1]
                chan_s = jnp.clip(pnxt, 0, n_ch * n_vc - 1) // n_vc
                prim_occ = size[lane_base + chan_s * n_vc + bv]
                best_occ = slots - score[qrows, j]    # slots + 1 when no cand
                prim_take = on_path & (pnxt >= 0) & ~consume_q \
                    & (prim_occ < slots) & (prim_occ <= best_occ + 4)
                if faulted:
                    prim_take = prim_take & (alive[ph, chan_s] > 0)
                use_esc = (vc_q == 0) | (stall >= patience) \
                    | (~has_cand & ~prim_take)
                e_ch = esc[ph, arrive_node, dq]
                nxt_ch = jnp.where(use_esc, e_ch,
                                   jnp.where(prim_take, chan_s, best_ch))
                nxt_vc = jnp.where(use_esc, 0, bv)
                valid = nxt_ch >= 0
                if faulted:
                    valid = valid & (alive[ph, jnp.clip(nxt_ch, 0,
                                                        n_ch - 1)] > 0)
                tq = jnp.where(consume_q | ~valid, -1,
                               lane_base
                               + jnp.clip(nxt_ch, 0, n_ch - 1) * n_vc
                               + nxt_vc)
                fwd_ok = nonempty & ~consume_q & (tq >= 0) \
                    & (size[jnp.clip(tq, 0, NQ - 1)] < slots)
            else:
                # pv packs channel * n_vc + vc per hop: one gather for both
                nxt = pv[hs, hd, hh + 1]
                tq = jnp.where(consume_q, -1, lane_base + nxt)
                if faulted:
                    tq = jnp.where(alive[ph, nxt // n_vc] > 0, tq, -1)
                    fwd_ok = nonempty & ~consume_q & (tq >= 0) \
                        & (size[jnp.clip(tq, 0, NQ - 1)] < slots)
                else:
                    fwd_ok = nonempty & ~consume_q \
                        & (size[jnp.clip(tq, 0, NQ - 1)] < slots)
            eligible = consume_q | fwd_ok

        with jax.named_scope("arbitrate"):
            eligible = eligible & jnp.repeat(busy == 0, n_vc)
            elig_cv = eligible.reshape(C, n_vc)
            offs = (rr[:, None] + jnp.arange(n_vc)[None, :]) % n_vc
            pri = jnp.take_along_axis(elig_cv, offs, axis=1)
            first = jnp.argmax(pri, axis=1)
            any_e = pri.any(axis=1)
            win_v = (rr + first) % n_vc
            win_q = jnp.arange(C) * n_vc + win_v
            win_valid = any_e
            rr = jnp.where(win_valid, (win_v + 1) % n_vc, rr)

            w_word = hw[win_q]
            w_tag = (w_word >> _TAG_SHIFT) & 1
            w_consume = consume_q[win_q] & win_valid
            w_target = jnp.where(win_valid & ~w_consume, tq[win_q], -1)

        with jax.named_scope("crossbar"):
            cand = win_valid & ~w_consume & (w_target >= 0)
            tgt = jnp.clip(w_target, 0, NQ - 1)
            first = jnp.full((NQ + 1,), C, jnp.int32) \
                .at[jnp.where(cand, tgt, NQ)] \
                .min(jnp.arange(C, dtype=jnp.int32))
            w_push = cand & (first[tgt] == jnp.arange(C))
            w_pop = w_consume | w_push
            busy = jnp.where(w_pop, flits - 1, jnp.maximum(busy - 1, 0))

        with jax.named_scope("push"):
            p_slot = (head[tgt] + size[tgt]) % slots
            if adaptive:
                w_hh = (w_word >> _HOP_SHIFT) & _HOP_MASK
                push_word = jnp.where(w_hh >= _HOP_MASK, w_word,
                                      w_word + (1 << _HOP_SHIFT))
            else:
                push_word = w_word + (1 << _HOP_SHIFT)

        with jax.named_scope("inject"):
            measure = i >= warmup
            key, k1, k2, k3 = jax.random.split(key, 4)
            if phased:
                thr = (rates[:, None] * src_rate[phz][None, :]).reshape(N)
                fp, fa = fprob[phz], falias[phz]
            else:
                thr, fp, fa = thresh, fprob, falias
            if bursty:
                on = ((i + phs) % period) < on_cycles
                want = jax.random.uniform(k1, (N,)) \
                    < thr * jnp.where(on, g_on, g_off)
            else:
                want = jax.random.uniform(k1, (N,)) < thr
            u1 = jax.random.uniform(k2, (N,))
            dg = deg[srcs]
            j = jnp.minimum((u1 * dg.astype(jnp.float32)).astype(jnp.int32),
                            dg - 1)
            f0 = src_ptr[srcs] + jnp.maximum(j, 0)
            u2 = jax.random.uniform(k3, (N,))
            fid = jnp.where(u2 < fp[f0], f0, fa[f0])
            dsts = fdst[fid]
            cv0 = pv[srcs, dsts, 0]
            if adaptive or faulted:
                ch0 = jnp.clip(cv0, 0, n_ch * n_vc - 1) // n_vc
                ok0 = (alive[ph, ch0] > 0) if faulted \
                    else jnp.ones((N,), bool)
                if adaptive:
                    iv = 1 + dsts % (n_vc - 1)
                    e0 = esc[ph, srcs, dsts]
                    cv0 = jnp.where(ok0, ch0 * n_vc + iv,
                                    jnp.maximum(e0, 0) * n_vc)
                    ok0 = ok0 | (e0 >= 0)
                iq = lane_q + jnp.clip(cv0, 0, n_ch * n_vc - 1)
            else:
                iq = lane_q + jnp.clip(cv0, 0, n_ch * n_vc - 1)
            i_pop = (w_pop[iq // n_vc]
                     & (win_q[iq // n_vc] == iq)).astype(jnp.int32)
            i_push = (first[iq] < C).astype(jnp.int32)
            has_space = size[iq] - i_pop + i_push < slots
            inj = want & has_space & (dg > 0)
            if adaptive or faulted:
                inj = inj & ok0
            i_slot = (head[iq] + size[iq] + i_push) % slots
            inj_word = _pack(srcs, dsts, jnp.zeros((N,), jnp.int32),
                             measure & inj)

        with jax.named_scope("scatter"):
            all_rows = jnp.concatenate([jnp.where(w_push, tgt, NQ),
                                        jnp.where(inj, iq, NQ)])
            all_slots = jnp.concatenate([p_slot, i_slot])
            all_words = jnp.concatenate([push_word, inj_word])
            q = q.at[all_rows, all_slots].set(all_words, mode="drop")

            popq = jnp.where(w_pop, win_q, NQ)
            d_rows = jnp.concatenate([popq, all_rows])
            d_vals = jnp.concatenate([jnp.full((C,), -1, jnp.int32),
                                      jnp.ones((C + N,), jnp.int32)])
            size = size.at[d_rows].add(d_vals, mode="drop")
            head = head.at[popq].add(1, mode="drop") % slots

        with jax.named_scope("counters"):
            meas = jnp.where(measure, 1, 0)
            cons_lane = w_consume.reshape(R, n_ch).sum(axis=1)
            inj_lane = inj.reshape(R, n).sum(axis=1)
            offered = offered + meas * want.reshape(R, n).sum(axis=1)
            accepted = accepted + meas * inj_lane
            tagged = tagged + (w_consume & (w_tag == 1)).reshape(
                R, n_ch).sum(axis=1)
            consumed_meas = consumed_meas + meas * cons_lane
            consumed = consumed + cons_lane
            injected = injected + inj_lane

            if T:
                # dense words carry (src, dst): attribute via the pair map
                # (tof[fid] == tmap[srcs, dsts] by construction, so the CSR
                # kernel's counters stay bit-identical)
                ws = w_word & _FIELD_MASK
                wd = (w_word >> _DST_SHIFT) & _FIELD_MASK
                t_w = tmap[ws, wd]
                ok_w = w_consume & (t_w >= 0)
                rowc = (jnp.arange(C) // n_ch) * T + jnp.clip(t_w, 0, T - 1)
                cons_t = cons_t.at[rowc].add(ok_w.astype(jnp.int32))
                consm_t = consm_t.at[rowc].add(
                    (ok_w & measure).astype(jnp.int32))
                t_i = tof[fid]
                rowi = (jnp.arange(N) // n) * T + jnp.clip(t_i, 0, T - 1)
                inj_t = inj_t.at[rowi].add(
                    (inj & (t_i >= 0)).astype(jnp.int32))

            if adaptive:
                popped = w_pop[qrows // n_vc] & (win_q[qrows // n_vc] == qrows)
                stall = jnp.where(nonempty & ~popped, stall + 1, 0)
                escaped = escaped + (w_push & (tgt % n_vc == 0)
                                     & (win_q % n_vc != 0)).reshape(
                    R, n_ch).sum(axis=1)

        with jax.named_scope("watchdog"):
            pop_lane = w_pop.reshape(R, n_ch).sum(axis=1)
            hops = hops + pop_lane          # packet-hops: pops of any queue
            progress = (pop_lane > 0) | (inj_lane > 0)
            wstall = jnp.where((injected - consumed > 0) & ~progress,
                               wstall + 1, 0)
            stalled_at = jnp.where((wstall >= watchdog) & (stalled_at < 0),
                                   i, stalled_at)
        return (i + 1, q, head, size, rr, busy, key, stall, wstall,
                stalled_at,
                (offered, accepted, tagged, consumed_meas, consumed,
                 injected, escaped, hops, inj_t, cons_t, consm_t))

    stats0 = (jnp.zeros((R,), jnp.int32),) * 8 \
        + (jnp.zeros((R * T,), jnp.int32),) * 3
    stall0 = jnp.zeros((NQ if adaptive else 1,), jnp.int32)
    carry = (jnp.int32(0), q, head, size, rr, busy, key, stall0,
             jnp.zeros((R,), jnp.int32), jnp.full((R,), -1, jnp.int32),
             stats0)

    def cond(carry):
        return (carry[0] < cycles) & ~jnp.all(carry[8] >= watchdog)

    carry = jax.lax.while_loop(cond, cycle, carry)
    q, head, size = carry[1], carry[2], carry[3]
    stalled_at = carry[9]
    (offered, accepted, tagged, consumed_meas, consumed, injected,
     escaped, hops, inj_t, cons_t, consm_t) = carry[-1]
    if T:
        # per-tenant end-of-run occupancy from the final ring buffers:
        # slot j of queue r holds a live word iff (j - head) % slots
        # < size -- exact, so injected == consumed + in_flight per tenant
        occ = ((jnp.arange(slots)[None, :] - head[:, None]) % slots) \
            < size[:, None]
        tw = word_tenant(q)                             # (NQ, slots)
        rows = (jnp.arange(NQ) // (n_ch * n_vc))[:, None] * T \
            + jnp.clip(tw, 0, T - 1)
        infl_t = jnp.zeros((R * T,), jnp.int32) \
            .at[rows].add((occ & (tw >= 0)).astype(jnp.int32))
    else:
        infl_t = jnp.zeros((0,), jnp.int32)
    return (offered, accepted, tagged, consumed_meas, consumed, injected,
            escaped, hops, size.reshape(R, -1).sum(axis=1), stalled_at,
            inj_t, cons_t, consm_t, infl_t, carry[0])


# The device programs the sweep kernels compile to, by the names that the
# profiler's trace gives their modules (followed there by a fingerprint in
# parentheses). Inside them the phases of the cycle body carry named
# scopes (route, arbitrate, crossbar, push, inject, scatter, counters,
# watchdog), which name each device operation's ``tf_op`` in the trace.
KERNEL_PROGRAMS = tuple(f"jit_{f.__name__}"
                        for f in (_sweep_csr, _sweep_dense))


def _compiled_flows(traffic, tables: SimTables) -> CompiledFlowTraffic:
    """Compile any accepted traffic input onto the table's flow slots."""
    if isinstance(traffic, CompiledFlowTraffic):
        return traffic
    t = tables.csr()
    ct = compile_flow_traffic(traffic, t.src_indptr, t.dst)
    if ct.prob.shape[-1] != t.n_flows:
        raise ValueError("flow traffic does not match the path table")
    return ct


@dataclasses.dataclass
class AdaptiveSpec:
    """Precomputed adaptive-routing tables for the sweep kernels.

    ``esc``/``minmask`` are stacked (2, n, n): plane 0 is the pre-fault
    network, plane 1 the post-fault survivors (identical when no fault is
    injected). ``outch`` is the fixed per-node out-channel slot layout --
    CSR out-adjacency order, fault-independent, so ``minmask`` bit ``j``
    always refers to the same physical channel.
    """
    esc: np.ndarray       # (2, n, n) int32: escape next-channel, -1 none
    outch: np.ndarray     # (n, D) int32: out-channels per node, -1 pad
    minmask: np.ndarray   # (2, n, n) uint8: bit j <=> outch[u, j] minimal

    @property
    def D(self) -> int:
        return self.outch.shape[1]


def adaptive_spec(topo: Topology,
                  dead_channels=None) -> AdaptiveSpec:
    """Build the escape + minimal-alternate tables for adaptive sweeps.

    When ``dead_channels`` is given, plane 1 of the stacked tables is
    recomputed over the survivors (escape tree re-rooted around the
    fault, minimal masks re-derived from surviving distances) -- the
    kernel switches planes at the fault cycle.
    """
    from repro.core.routing import adaptive_route
    from repro.core.vcalloc import escape_routes
    e0 = escape_routes(topo)
    a0 = adaptive_route(topo)
    if not e0.connected:
        raise ValueError("pre-fault escape tree does not span the "
                         "network")
    dc = _dead_channel_array(dead_channels)
    if dc is None:
        e1, a1 = e0, a0
    else:
        e1 = escape_routes(topo, dc)
        a1 = adaptive_route(topo, dc)
    return AdaptiveSpec(
        np.stack([e0.esc_next, e1.esc_next]).astype(np.int32),
        a0.outch.astype(np.int32),
        np.stack([a0.minmask, a1.minmask]).astype(np.uint8))


@dataclasses.dataclass
class _SweepCall:
    """One sweep's kernel execution: ``fn(*args, **static)`` is exactly
    what :func:`sweep` runs (``fn`` is None when the traffic routes no
    flow), plus what it needs to decode the result. ``args`` are host
    arrays, but for the PRNG key, which JAX makes on the device;
    :func:`sweep` puts them on the device in one place."""
    fn: Optional[Callable]
    args: tuple
    static: dict
    rates: np.ndarray
    tenants: Optional[TenantMap]
    array_bytes: int


def _sweep_call(tables: SimTables, rates: Sequence[float], traffic, *,
                cycles: int, warmup: int, slots: int, seed: int,
                flits: int, kernel: str, adaptive: Optional[AdaptiveSpec],
                fault: Optional[Tuple[int, Sequence[int]]], patience: int,
                watchdog: int) -> _SweepCall:
    """Validate a :func:`sweep` request and assemble its kernel call.

    Both :func:`sweep` and the ahead-of-time compile tests go through
    here, so a test lowers exactly the program a sweep would run."""
    if MAXHOP > _HOP_MASK:
        raise ValueError(f"packed packet words support MAXHOP <= "
                         f"{_HOP_MASK}")
    if patience < 1:
        raise ValueError("patience must be >= 1")
    if watchdog < 1:
        raise ValueError("watchdog must be >= 1")
    adaptive_on = adaptive is not None
    if adaptive_on and tables.n_vc < 2:
        raise ValueError("adaptive routing reserves VC 0 as the escape "
                         "lane and needs n_vc >= 2")
    faulted = fault is not None
    t_fault = 0
    dead = None
    if faulted:
        t_fault, dead_in = fault
        t_fault = int(t_fault)
        if not 0 <= t_fault <= cycles:
            raise ValueError(f"fault cycle {t_fault} outside "
                             f"[0, {cycles}]")
        dead = _dead_channel_array(dead_in)
        if dead is not None and ((dead < 0).any()
                                 or (dead >= tables.n_ch).any()):
            bad = dead[(dead < 0) | (dead >= tables.n_ch)]
            raise ValueError(f"unknown channel ids {bad.tolist()} "
                             f"(topology has {tables.n_ch} channels)")
    alive_np = np.ones((2, tables.n_ch), np.int32)
    if faulted and dead is not None:
        alive_np[1, dead] = 0
    if adaptive_on:
        esc_np = np.ascontiguousarray(adaptive.esc, np.int32)
        outch_np = np.ascontiguousarray(adaptive.outch, np.int32)
        minmask_np = np.ascontiguousarray(adaptive.minmask, np.uint8)
        D = adaptive.D
        if esc_np.shape != (2, tables.n, tables.n):
            raise ValueError("adaptive spec built for a different "
                             "topology")
    else:
        esc_np = np.zeros((2, 1, 1), np.int32)
        outch_np = np.zeros((1, 1), np.int32)
        minmask_np = np.zeros((2, 1, 1), np.uint8)
        D = 1
    ct = _compiled_flows(traffic, tables)
    burst = ct.burst
    bursty = burst is not None
    if bursty:
        on_cycles, g_on, g_off, phase_np = burst.realize(tables.n)
        period = int(burst.period)
    else:
        period, on_cycles, g_on, g_off = 0, 0, 1.0, 1.0
        phase_np = np.zeros(tables.n, np.int32)
    phased = ct.phases > 0
    if phased:
        phase_of_np = np.asarray(ct.phase_of, np.int32)
        p_period = int(len(phase_of_np))
    else:
        phase_of_np = np.zeros(1, np.int32)
        p_period = 1
    tenants = ct.tenants
    T = tenants.n_tenants if tenants is not None else 0
    if T:
        tmap_np = np.asarray(tenants.pair_tenant, np.int32)
        t_csr = tables.csr()
        fsrc = np.repeat(np.arange(tables.n),
                         np.diff(t_csr.src_indptr).astype(np.int64))
        tof_np = tmap_np[fsrc, np.asarray(t_csr.dst, np.int64)]
    else:
        tmap_np = np.zeros((1, 1), np.int32)
        tof_np = np.zeros(1, np.int32)
    rates = np.asarray(list(rates), np.float32)
    R = len(rates)
    NQ = R * tables.n_ch * tables.n_vc
    F = int(ct.prob.shape[-1])
    state_bytes = NQ * slots * 4 + NQ * 8 + R * tables.n_ch * 8
    if adaptive_on:
        state_bytes += NQ * 4     # per-queue stall counters
    traffic_bytes = (ct.src_indptr.nbytes + ct.deg.nbytes + ct.prob.nbytes
                     + ct.alias.nbytes + ct.src_rate.nbytes)
    aux_bytes = (esc_np.nbytes + outch_np.nbytes + minmask_np.nbytes
                 + alive_np.nbytes + phase_np.nbytes + tof_np.nbytes
                 + tmap_np.nbytes + phase_of_np.nbytes)
    if F == 0:
        return _SweepCall(None, (), {}, rates, tenants,
                          state_bytes + traffic_bytes)
    if kernel == "csr":
        t = tables.csr()
        if t.n_flows > _FLOW_MASK:
            raise ValueError(f"packed packet words support F <= "
                             f"{_FLOW_MASK} flows")
        pvf = (t.chan.astype(np.int64) * tables.n_vc
               + t.vc.astype(np.int64)).astype(np.int32)
        hptr = t.hop_indptr[:-1].astype(np.int32)
        lenm1 = (np.diff(t.hop_indptr) - 1).astype(np.int32)
        if len(lenm1) and (lenm1 < 0).any():
            raise ValueError(
                "path table contains zero-length (lost) flow slots -- "
                "the kernel samples traffic over flow slots and cannot "
                "inject a packet with no route; compact a degraded "
                "serving table first (CSRPathTable.compact() drops "
                "lost pairs and remaps flow ids)")
        dstN = np.asarray(t.dst, np.int32)   # flow -> destination node
        route_bytes = pvf.nbytes + hptr.nbytes + lenm1.nbytes + dstN.nbytes
        args = (tables.ch_dst, pvf, hptr, lenm1, dstN)
        fn = _sweep_csr
    elif kernel == "dense":
        if tables.n > _FIELD_MASK:
            raise ValueError(f"the dense kernel's packed packet words "
                             f"support n <= {_FIELD_MASK}")
        # composite per-hop (channel * n_vc + vc) table: one kernel gather
        pv = np.where(tables.path < 0, -1,
                      tables.path * tables.n_vc
                      + tables.vcs.astype(np.int32)).astype(np.int32)
        fdst = np.asarray(tables.csr().dst, np.int32)
        route_bytes = pv.nbytes + fdst.nbytes
        args = (tables.ch_dst, pv, fdst)
        fn = _sweep_dense
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    args = args + (
        ct.src_indptr[:-1], ct.deg, ct.prob, ct.alias, ct.src_rate, rates,
        jax.random.PRNGKey(seed), outch_np, minmask_np, esc_np, alive_np,
        np.int32(t_fault), np.float32(g_on), np.float32(g_off),
        np.asarray(phase_np, np.int32), tof_np, tmap_np, phase_of_np)
    static = dict(R=R, n=tables.n, n_ch=tables.n_ch, n_vc=tables.n_vc,
                  slots=slots, cycles=cycles, warmup=warmup, flits=flits,
                  adaptive=adaptive_on, faulted=faulted, bursty=bursty,
                  patience=patience, watchdog=watchdog, D=D, period=period,
                  on_cycles=on_cycles, T=T, phased=phased,
                  p_period=p_period)
    return _SweepCall(fn, args, static, rates, tenants,
                      state_bytes + traffic_bytes + route_bytes
                      + aux_bytes)


def sweep(tables: SimTables, rates: Sequence[float],
          traffic: Optional[Union[TrafficPattern, CompiledTraffic,
                                  CompiledFlowTraffic,
                                  PhasedTraffic]] = None,
          cycles: int = 6000, warmup: int = 2000, slots: int = 128,
          seed: int = 0, flits: int = 4, kernel: str = "csr",
          stats: Optional[dict] = None,
          adaptive: Optional[AdaptiveSpec] = None,
          fault: Optional[Tuple[int, Sequence[int]]] = None,
          patience: int = 64, watchdog: int = 512) -> List[Dict]:
    """Simulate every rate in one batched (lane-flattened) kernel
    execution; one dict per rate.

    ``kernel="csr"`` (default) gathers routes from the CSR hop arrays
    and never touches the dense ``(n, n, MAXHOP)`` tables;
    ``kernel="dense"`` runs the legacy dense-gather kernel on the same
    flow-slot traffic tables and RNG stream -- the counters of the two
    kernels are bit-identical (the CSR parity tests rely on it). A
    ``stats`` dict, when given, records the kernel used and the peak
    device-array bytes staged per call under ``"array_bytes"``.

    ``adaptive`` (an :func:`adaptive_spec` result) switches both kernels
    to occupancy-driven minimal adaptive routing with the VC0 escape
    lane; requires ``n_vc >= 2`` tables (VC0 reserved -- allocate with
    ``reserve_escape=True``). ``fault=(t, dead_channels)`` kills the
    given channels at cycle ``t`` mid-sweep: dead channels stop
    accepting forwards/injections (their receive queues still drain),
    and with ``adaptive`` set, in-flight packets re-resolve onto
    surviving alternates or the re-rooted escape tree. ``patience`` is
    the per-queue stalled-cycles threshold before an adaptive head
    diverts to the escape VC; ``watchdog`` is the zero-progress window
    after which a lane is declared stalled (``stalled_at`` per rate,
    ``stats["cycles_run"]`` < ``cycles`` when every lane wedged and the
    sweep aborted early).

    A :class:`PhasedTraffic` input switches both kernels to trace
    replay: the spatial demand phase follows the compiled schedule
    cycle by cycle. A pattern carrying a
    :class:`~repro.core.traffic.TenantMap` (from
    :func:`~repro.core.traffic.compose_tenants`) adds a ``"tenants"``
    entry to every rate dict -- per-tenant injected / consumed /
    in-flight packet counts (exact conservation: injected == consumed +
    in-flight) and delivered throughput per tenant node.

    Every rate dict counts ``hops``, the packet-hops of the whole run:
    pops from a channel queue, each a packet moving on to its next
    channel or being consumed. The call records the program spans
    ``netsim.sweep`` and its stages ``.assemble`` (validation and host
    arrays), ``.upload`` (the arguments put on the device, with the
    counter ``netsim.sweep.upload_bytes``), ``.run`` (dispatch until
    the outputs are ready) and ``.decode`` (the rate dicts, with the
    counter ``netsim.sweep.hops``).
    """
    with obs.span("netsim.sweep"):
        with obs.span("netsim.sweep.assemble"):
            call = _sweep_call(tables, rates, traffic, cycles=cycles,
                               warmup=warmup, slots=slots, seed=seed,
                               flits=flits, kernel=kernel,
                               adaptive=adaptive, fault=fault,
                               patience=patience, watchdog=watchdog)
        if stats is not None:
            stats["kernel"] = kernel
            stats["array_bytes"] = max(stats.get("array_bytes", 0),
                                       call.array_bytes)
        if call.fn is None:
            if stats is not None:
                stats["cycles_run"] = cycles
            return [{"rate": float(r), "offered": 0.0, "accepted": 0.0,
                     "delivered": 0.0, "delivered_tagged": 0.0,
                     "consumed_total": 0, "injected_total": 0,
                     "in_flight": 0, "escaped": 0, "stalled_at": -1,
                     "hops": 0}
                    for r in call.rates]
        with obs.span("netsim.sweep.upload"):
            args = jax.block_until_ready(jax.device_put(call.args))
            obs.count("netsim.sweep.upload_bytes",
                      sum(a.nbytes for a in call.args))
        with obs.span("netsim.sweep.run"):
            out = jax.block_until_ready(call.fn(*args, **call.static))
        with obs.span("netsim.sweep.decode"):
            lanes = _decode(call, out, tables.n, cycles - warmup, stats)
            obs.count("netsim.sweep.hops", sum(r["hops"] for r in lanes))
        return lanes


def _decode(call: _SweepCall, out: tuple, n: int, meas: int,
            stats: Optional[dict]) -> List[Dict]:
    """The kernel's outputs as one dict per rate; ``meas`` is the
    number of measured cycles and ``n`` the node count."""
    rates, tenants = call.rates, call.tenants
    T = call.static["T"]
    (off, acc, tagd, consm, cons, injd, escd, hops, infl, stalled,
     inj_t, cons_t, consm_t, infl_t) = (np.asarray(a) for a in out[:-1])
    cycles_run = int(out[-1])
    if stats is not None:
        stats["cycles_run"] = cycles_run
    trace = []
    for i, rate in enumerate(rates):
        trace.append({
            "rate": float(rate),
            "offered": float(off[i]) / meas / n,
            "accepted": float(acc[i]) / meas / n,
            # steady-state throughput: window consumption rate
            "delivered": float(consm[i]) / meas / n,
            # conservation-safe: only packets injected inside the window
            "delivered_tagged": float(tagd[i]) / meas / n,
            "consumed_total": int(cons[i]),
            "injected_total": int(injd[i]),
            "in_flight": int(infl[i]),
            # adaptive diagnostics: escape-lane diversions and the cycle
            # the lane's watchdog fired (-1 = never stalled)
            "escaped": int(escd[i]),
            "stalled_at": int(stalled[i]),
            # packet-hops: pops from any channel queue, each a packet
            # moving on to its next channel or being consumed
            "hops": int(hops[i]),
        })
        if T:
            # per-tenant accounting (exact conservation:
            # injected == consumed + in_flight for every tenant)
            tens = {}
            for t_id, name in enumerate(tenants.names):
                k = i * T + t_id
                tens[name] = {
                    "injected": int(inj_t[k]),
                    "consumed": int(cons_t[k]),
                    "in_flight": int(infl_t[k]),
                    "delivered": float(consm_t[k]) / meas
                    / max(int(tenants.n_nodes[t_id]), 1),
                }
            trace[-1]["tenants"] = tens
    return trace


def run(tables: SimTables, rate: float,
        traffic: Optional[Union[TrafficPattern, CompiledTraffic,
                                CompiledFlowTraffic,
                                PhasedTraffic]] = None,
        cycles: int = 6000, warmup: int = 2000, slots: int = 128,
        seed: int = 0, flits: int = 4, kernel: str = "csr",
        stats: Optional[dict] = None,
        adaptive: Optional[AdaptiveSpec] = None,
        fault: Optional[Tuple[int, Sequence[int]]] = None,
        patience: int = 64, watchdog: int = 512) -> Dict:
    """Single-rate convenience wrapper over :func:`sweep`."""
    return sweep(tables, [rate], traffic, cycles=cycles, warmup=warmup,
                 slots=slots, seed=seed, flits=flits, kernel=kernel,
                 stats=stats, adaptive=adaptive, fault=fault,
                 patience=patience, watchdog=watchdog)[0]


def saturation_point(tables: SimTables, step: float = 0.01,
                     max_rate: float = 1.0, deficit: float = 0.05,
                     cycles: int = 6000, warmup: int = 2000,
                     slots: int = 128, flits: int = 4,
                     traffic: Optional[Union[TrafficPattern,
                                             CompiledTraffic,
                                             CompiledFlowTraffic,
                                             PhasedTraffic]] = None,
                     seed: int = 0, kernel: str = "csr",
                     stats: Optional[dict] = None,
                     adaptive: Optional[AdaptiveSpec] = None,
                     patience: int = 64,
                     watchdog: int = 512) -> Tuple[float, List[Dict]]:
    """Saturation = last rate whose delivered throughput covers
    (1 - deficit) of offered, before the first shortfall.

    Two batched stages instead of a python loop of per-rate jit calls: a
    coarse sub-grid at half the cycle budget brackets the saturation rate,
    then the grid rates inside the bracketing cell run at full fidelity in
    a second batched execution. Each stage is one compile (cached per
    rate-count) + one device execution; only full-fidelity rates enter the
    returned trace. A bracketing error costs at most one grid step of
    saturation accuracy -- within the deficit criterion's own noise.

    The traffic pattern is compiled onto the table's flow slots once and
    shared by every stage; ``kernel``/``stats``/``adaptive`` forward to
    :func:`sweep` (mid-sweep faults do not -- a fault cycle is only
    meaningful against one fixed cycle budget, so fault studies call
    :func:`sweep` directly).
    """
    ct = _compiled_flows(traffic, tables)
    rates = np.arange(step, max_rate + 1e-9, step)
    stride = max(1, int(round(np.sqrt(len(rates)))))
    coarse_idx = list(range(stride - 1, len(rates), stride))
    if coarse_idx[-1] != len(rates) - 1:
        coarse_idx.append(len(rates) - 1)
    coarse = sweep(tables, rates[coarse_idx], ct,
                   cycles=max(cycles // 2, warmup // 2 + 1),
                   warmup=warmup // 2, slots=slots, seed=seed, flits=flits,
                   kernel=kernel, stats=stats, adaptive=adaptive,
                   patience=patience, watchdog=watchdog)

    def ok(r):
        return r["delivered"] >= (1 - deficit) * r["offered"]

    first_bad = next((i for i, r in enumerate(coarse) if not ok(r)),
                     None)
    if first_bad is None:
        lo, hi = max(len(rates) - stride, 0), len(rates)
    else:
        lo = coarse_idx[first_bad - 1] + 1 if first_bad >= 1 else 0
        hi = coarse_idx[first_bad] + 1
    # full-fidelity refinement; if the half-budget bracket overshot (its
    # lower edge already saturated at full fidelity), slide down a cell
    # until the window's first rate passes or the grid floor is reached
    trace: List[Dict] = []
    while True:
        fine = sweep(tables, rates[lo:hi], ct, cycles=cycles,
                     warmup=warmup, slots=slots, seed=seed, flits=flits,
                     kernel=kernel, stats=stats, adaptive=adaptive,
                     patience=patience, watchdog=watchdog)
        trace = fine + trace
        if lo == 0 or (fine and ok(fine[0])):
            break
        hi = lo
        lo = max(lo - stride, 0)
    sat = 0.0
    for r in trace:
        if ok(r):
            sat = r["delivered"]
        else:
            break
    return sat, trace


# ---------------------------------------------------------------------------
# DOR baseline on prismatic tori (XYZ order, dateline VC switching),
# vectorised over all (src, dst) pairs at once.
# ---------------------------------------------------------------------------


def dor_paths(topo: Topology) -> PathTable:
    """Dimension-ordered minimal routing on a torus with dateline VC rule:
    start on VC0, switch to VC1 after crossing a wrap link in any dim.

    Fully vectorised: the outer loop runs 3 axes x (dim // 2) steps; each
    step advances every still-moving pair simultaneously via a dense
    (u, v) -> channel lookup. No per-pair python loops, no dicts.
    """
    ch = Channels.from_topology(topo)
    pod = topo.pod
    n = topo.n
    X, Y, Z = pod.dims
    chan_of = np.full((n, n), -1, np.int64)
    chan_of[ch.src, ch.dst] = np.arange(ch.n)

    coords = pod.all_coords().astype(np.int64)
    cur = np.broadcast_to(coords[:, None, :], (n, n, 3)).copy()
    tgt = np.broadcast_to(coords[None, :, :], (n, n, 3))

    table = PathTable.empty(n, ch.n, 2)
    hops = table.hops
    vc = np.zeros((n, n), np.int8)
    for axis in range(3):
        dim = pod.dims[axis]
        delta = (tgt[..., axis] - cur[..., axis]) % dim
        step = np.where(2 * delta <= dim, 1, -1)
        count = np.where(step == 1, delta, dim - delta)
        for k in range(dim // 2):
            act = count > k
            if not act.any():
                break
            c_ax = cur[..., axis]
            nxt_ax = (c_ax + step) % dim
            nxt = cur.copy()
            nxt[..., axis] = nxt_ax
            u = cur[..., 0] + X * (cur[..., 1] + Y * cur[..., 2])
            v = nxt[..., 0] + X * (nxt[..., 1] + Y * nxt[..., 2])
            si, di = np.nonzero(act)
            cidx = chan_of[u[si, di], v[si, di]]
            if (cidx < 0).any():
                raise KeyError("DOR needs torus links along every axis")
            crossed = ((step == 1) & (nxt_ax == 0)) | \
                ((step == -1) & (c_ax == 0))
            vc = np.where(act & crossed, np.int8(1), vc)
            h = hops[si, di]
            table.path[si, di, h] = cidx.astype(np.int32)
            table.vcs[si, di, h] = vc[si, di]
            hops[si, di] = h + 1
            cur = np.where(act[..., None], nxt, cur)
    return table


def dor_tables(topo: Topology, n_vc: int = 2) -> SimTables:
    table = dor_paths(topo)
    table.n_vc = n_vc
    return build_tables(topo, table)


def at_tables(topo: Topology, at: ATResult, routed: RoutingResult,
              balance: Optional[bool] = True,
              stats: Optional[dict] = None,
              reserve_escape: bool = False) -> SimTables:
    """VC-allocate the routed paths and build simulator tables.

    Works on a copy of ``routed.table`` so the caller's RoutingResult is
    not mutated and the returned SimTables cannot be rewritten by later
    allocations on the same result. Both table layouts pass through
    unchanged (a CSR table stays CSR -- and feeds the CSR-native kernel
    without ever densifying).

    ``balance=None`` skips re-allocation and keeps the VC assignment
    already in the table -- the array and sharded path-selection engines
    emit each winning candidate's BFS state-path VCs, which are valid by
    construction (fast path for large pods / fault sweeps where the
    balanced re-allocation is not needed). ``stats`` is forwarded to
    :func:`~repro.core.vcalloc.allocate_vcs` (greedy dead-end
    counters). ``reserve_escape=True`` keeps VC 0 free for the adaptive
    escape lane (forwarded to the allocator; requires re-allocation,
    i.e. ``balance`` not None)."""
    from repro.core.vcalloc import allocate_vcs
    if reserve_escape and balance is None:
        raise ValueError("reserve_escape needs VC re-allocation "
                         "(balance=True or False)")
    table = routed.table.copy()
    if balance is not None:
        allocate_vcs(at, table, balance=balance, stats=stats,
                     reserve_escape=reserve_escape)
    table.n_vc = at.n_vc
    return build_tables(topo, table)
