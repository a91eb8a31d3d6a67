"""Plain reference for the expert-parallel decode demand.

It imports nothing of the program under test. From a traffic mix's
published router settings and a seed it routes one decode step token by
token and group by group, places the experts on chips, and gives every
phase's byte matrix, per-source rates and cycles.

The semantics it implements, which the program must reproduce bit for
bit:

- ``SeedSequence(seed)`` spawns ``layers + 1`` streams; a NumPy
  ``default_rng`` on the first draws each token's topic (``choice`` over
  ``TOPICS`` topics with Zipf weights ``1/k``), and one on stream
  ``l + 1`` draws layer ``l``'s ``A`` (topics x experts) and then ``eps``
  (tokens x experts), both standard normal float64;
- a token's scores are ``sigmoid(A[topic] + eps)``; a group scores the
  sum of its two best experts; the token keeps the ``topk_group`` best
  groups and its ``num_experts_per_tok`` best experts in them, ties to
  the lower index; the selection bias is 0;
- token ``t`` lives on chip ``t // tokens_per_chip`` and expert ``e`` on
  chip ``e // experts_per_chip``; a token sends one copy to each distinct
  chip other than its own that holds one of its experts;
- layer ``l`` is a dispatch phase (copies times the dispatch bytes) and
  then a combine phase (the copies transposed, times the combine bytes);
- a phase's source rates are ``float32(row_bytes * n / phase_bytes)``
  and its cycles ``period * phase_bytes // step_bytes``, at least 1, the
  last phase taking the rest of the period.

``dtype`` sets the precision of the router's scores: the traffic states
float64; ``"float32"`` is the lower-precision control.
"""
from __future__ import annotations

import numpy as np

TOPICS = 32


def route(params: dict, tokens: int, layers: int, seed: int,
          dtype: str = "float64") -> list:
    """``[layer][token]``: the sorted expert ids each token is routed to."""
    pub = params["published"]
    E, G = pub["n_routed_experts"], pub["n_group"]
    keep_groups, k = pub["topk_group"], pub["num_experts_per_tok"]
    width = E // G
    streams = np.random.SeedSequence(seed).spawn(layers + 1)
    w = np.array([1.0 / t for t in range(1, TOPICS + 1)])
    topic = np.random.default_rng(streams[0]).choice(TOPICS, size=tokens,
                                                     p=w / w.sum())
    out = []
    for layer in range(layers):
        rng = np.random.default_rng(streams[layer + 1])
        A = rng.standard_normal((TOPICS, E))
        eps = rng.standard_normal((tokens, E))
        sets = []
        for t in range(tokens):
            logit = (A[topic[t]] + eps[t]).astype(dtype)
            s = 1.0 / (1.0 + np.exp(-logit))
            score = []
            for g in range(G):
                best = sorted(s[g * width:(g + 1) * width], reverse=True)
                score.append(best[0] + best[1])
            groups = sorted(range(G), key=lambda g: (-score[g], g))
            cand = [e for g in groups[:keep_groups]
                    for e in range(g * width, (g + 1) * width)]
            sets.append(sorted(sorted(cand, key=lambda e: (-s[e], e))[:k]))
        out.append(sets)
    return out


def copies(params: dict, routes: list, n: int) -> list:
    """``[layer]``: (n, n) int64 copies from each chip to each other."""
    tpc, epc = params["tokens_per_chip"], params["experts_per_chip"]
    out = []
    for sets in routes:
        c = np.zeros((n, n), np.int64)
        for t, experts in enumerate(sets):
            src = t // tpc
            for chip in sorted({e // epc for e in experts}):
                if chip != src:
                    c[src, chip] += 1
        out.append(c)
    return out


def phases(params: dict, copies_of: list, n: int):
    """Each phase's (n, n) float64 byte matrix and (n,) float32 source
    rates, and the phases' cycles."""
    per_copy = params["bytes_per_copy"]
    mats = []
    for c in copies_of:
        mats += [c * per_copy["dispatch"], c.T * per_copy["combine"]]
    totals = [int(m.sum()) for m in mats]
    step = sum(totals)
    cycles = [max(1, params["period"] * b // step) for b in totals[:-1]]
    cycles.append(params["period"] - sum(cycles))
    out = []
    for m, total in zip(mats, totals):
        rate = np.array([np.float32(int(m[s].sum()) * n / total)
                         for s in range(n)], np.float32)
        out.append((m.astype(np.float64), rate))
    return out, cycles
