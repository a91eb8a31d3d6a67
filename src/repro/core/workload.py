"""Workload co-design: parallelization-derived demand -> specialized TONS.

The generic synthesis LP maximises a uniform all-to-all throughput
proxy; TopoOpt and ACOS (PAPERS.md) show the real win comes from
co-optimising the fabric with the *training job*. This module closes
that loop end to end:

1. :func:`collective_mix` -- an analytic per-collective wire-byte
   estimate straight from a :class:`~repro.configs.base.ModelConfig` +
   :class:`~repro.configs.base.ShapeConfig` (DP gradient all-reduce, TP
   activation all-gather/reduce-scatter, MoE token all-to-all), used
   whenever no measured dry-run JSON exists on disk;
2. :func:`workload_demand` -- dry-run measurements when available
   (:func:`repro.core.demand.from_dryrun`), the analytic mix otherwise,
   both through the same :func:`repro.core.demand.from_mix` mapping, so
   the two sources are interchangeable;
3. :func:`synthesize_for_workload` -- the demand's translation-invariant
   ``weight_fn`` becomes ``pair_weight`` for the symmetric synthesis LP:
   a fabric optimised for *this* job's traffic;
4. :func:`replay_trace` -- the workload's one-step collective sequence
   as a :class:`~repro.core.traffic.PhasedTraffic` (in-cube TP/EP
   all-to-all phase -> cross-cube DP-ring phase -> uniform background,
   durations proportional to wire bytes) for the simulator's
   trace-replay mode; :func:`ep_decode_trace` -- a wide expert-parallel
   decode step routed token by token by a published router
   (:data:`ROUTERS`), one dispatch and one combine phase per MoE layer;
5. :func:`evaluate_workload` -- demand-weighted MCF + trace-replay
   saturation of any topology on a workload, routed through
   :func:`repro.core.pipeline.route_pod` (the headline
   specialized-vs-generic-vs-torus comparison in bench_workload / fig11);
6. :func:`workload_tenant` -- a sub-pod slice of a workload's demand as
   a :class:`~repro.core.traffic.TenantSpec` for multi-job composition.

MoE archs come out all-to-all-heavy (same-cube demand), dense archs
all-reduce-heavy (cross-cube DP rings) -- so their specialized fabrics
genuinely differ, which is the point.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.configs.registry import get_config, get_shape
from repro.core import demand, obs
from repro.core.demand import WorkloadDemand, weighted_mcf
from repro.core.pipeline import PipelineConfig, route_pod
from repro.core.topology import CUBE, Pod
from repro.core.traffic import PhasedTraffic, TenantSpec, TrafficPattern

_BF16 = 2.0     # bytes per element on the wire


def collective_mix(model: ModelConfig, shape: ShapeConfig
                   ) -> Dict[str, float]:
    """Analytic per-collective wire-byte estimate for one step.

    Deliberately coarse -- it only needs to get the *ratios* right for
    the demand-weight mapping (:func:`repro.core.demand.from_mix`
    normalises to relative levels):

    - TP activation collectives: one all-gather + one reduce-scatter of
      the token activations per layer's mixer/FFN pair;
    - MoE dispatch + combine: ``top_k``-way token all-to-all, twice per
      MoE layer;
    - DP gradient sync (train shapes only): ring all-reduce over the
      parameters, ~2x param bytes on the wire.

    Decode shapes process one new token per step, so token-proportional
    terms collapse while the (absent, in decode) gradient term stays 0
    -- the mix degrades gracefully to TP-dominated, which is what a
    decode step actually looks like.
    """
    steps_tokens = shape.global_batch * (
        shape.seq_len if shape.kind in ("train", "prefill") else 1)
    D = float(model.d_model)
    enc_dec = model.family == "encdec"
    layers = (model.enc_layers + model.dec_layers) if enc_dec \
        else model.n_layers
    n_moe = 0 if enc_dec else sum(
        1 for i in range(layers) if model.ffn_kind(i) == "moe")
    wires = {"all-to-all": 0.0, "all-reduce": 0.0,
             "all-gather": 0.0, "reduce-scatter": 0.0}
    act = steps_tokens * D * _BF16
    wires["all-gather"] += layers * act
    wires["reduce-scatter"] += layers * act
    if n_moe and model.top_k:
        # dispatch + combine, top_k expert copies per token
        wires["all-to-all"] += 2 * n_moe * act * model.top_k
    if shape.kind == "train":
        wires["all-reduce"] += 2 * model.param_count() * _BF16
    return wires


def workload_demand(podspec, arch: str, shape: str = "train_4k",
                    dryrun_dir: str = "benchmarks/results/dryrun",
                    mesh: str = "single_pod_16x16") -> WorkloadDemand:
    """Demand weights for a registered arch on a pod: measured dry-run
    collectives when the JSON exists, the analytic mix otherwise --
    identical mapping either way (:func:`repro.core.demand.from_mix`).
    """
    from pathlib import Path
    f = Path(dryrun_dir) / f"{arch}__{shape}__{mesh}.json"
    if f.exists():
        return demand.from_dryrun(podspec, arch, shape,
                                  dryrun_dir=dryrun_dir, mesh=mesh)
    model = get_config(arch).model
    return demand.from_mix(Pod(podspec),
                           collective_mix(model, get_shape(shape)))


def synthesize_for_workload(podspec, arch: str, shape: str = "train_4k",
                            wd: Optional[WorkloadDemand] = None,
                            **synth_kw):
    """Synthesize a fabric specialized for one workload's demand.

    The demand's ``weight_fn`` (translation-invariant by construction:
    same-cube membership + cube-offset rings) rides into the symmetric
    synthesis LP as ``pair_weight``, so the orbit reductions still
    apply and only the objective changes. Returns
    ``(SynthesisResult, WorkloadDemand)``; extra kwargs forward to
    :func:`repro.core.synthesis.synthesize`.
    """
    from repro.core.synthesis import synthesize
    if wd is None:
        wd = workload_demand(podspec, arch, shape)
    res = synthesize(podspec, symmetric=True, pair_weight=wd.weight_fn(),
                     **synth_kw)
    return res, wd


def replay_trace(wd: WorkloadDemand, period: int = 256,
                 min_cycles: int = 8) -> PhasedTraffic:
    """The workload's one-step collective sequence as a cyclic phased
    demand schedule for the simulator.

    Up to three phases -- in-cube TP/EP all-to-all, cross-cube DP ring,
    uniform background -- each phase's spatial pattern the
    corresponding single-component :class:`WorkloadDemand` matrix, so a
    trace replay stresses the fabric the way the training step does:
    bursts of concentrated collective traffic, not a stationary blend.

    Phase durations are proportional to per-node wire *volume* (demand
    level x partner count, i.e. the component's row mass), floored at
    ``min_cycles`` and summing to ~``period`` cycles: at a fixed
    per-node injection bandwidth, a phase moving k times the bytes
    occupies k times the cycles. (Weight *levels* alone would misprice
    broad components -- a uniform floor touching every pair moves far
    more volume per node than one ring partner at a higher level.)
    Keep ``min_cycles`` small relative to ``period``: it exists only to
    stop a phase degenerating to zero cycles, and a large floor hands
    low-volume phases schedule share their bytes don't justify.
    """
    pod = wd.pod
    comps: List[Tuple[str, WorkloadDemand]] = []
    if wd.w_same_cube > 0:
        comps.append(("a2a", WorkloadDemand(
            pod, w_same_cube=wd.w_same_cube, w_uniform=0.0)))
    if wd.w_ring > 0:
        comps.append(("ring", WorkloadDemand(
            pod, w_ring=wd.w_ring, w_uniform=0.0)))
    comps.append(("background", WorkloadDemand(
        pod, w_uniform=max(float(wd.w_uniform), 1e-6))))
    patterns = []
    masses = []
    for name, d in comps:
        m = d.matrix()
        patterns.append(TrafficPattern.from_matrix(name, m))
        masses.append(float(m.sum()) / pod.n)      # per-node volume
    total = sum(masses)
    cycles = [max(min_cycles, int(round(period * m / total)))
              for m in masses]
    return PhasedTraffic("trace", tuple(patterns), tuple(cycles))


@dataclasses.dataclass(frozen=True)
class RouterSpec:
    """The published router settings of a routed-expert MoE: each token
    scores every routed expert, keeps the ``topk_group`` best of
    ``n_group`` equal expert groups (a group scores the sum of its two
    best experts) and takes its ``experts_per_token`` best experts
    inside them. Shared experts see every token where it is."""
    n_routed_experts: int
    experts_per_token: int
    n_group: int
    topk_group: int
    n_shared_experts: int
    scoring: str
    hidden_size: int
    moe_layers: int

    @property
    def dispatch_bytes(self) -> int:
        """One token copy on its way to an expert: ``hidden_size`` FP8
        values and one float32 scale per 128 of them."""
        return self.hidden_size + 4 * (self.hidden_size // FP8_BLOCK)

    @property
    def combine_bytes(self) -> int:
        """One expert output on its way back: ``hidden_size`` BF16."""
        return 2 * self.hidden_size


FP8_BLOCK = 128     # FP8 values per float32 scale on the wire
TOPICS = 32         # clusters of decode hidden states, Zipf-weighted

# https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json:
# n_routed_experts, num_experts_per_tok, n_group, topk_group,
# n_shared_experts, scoring_func, hidden_size; 61 layers of which
# first_k_dense_replace = 3 are dense
ROUTERS: Dict[str, RouterSpec] = {
    "DeepSeek-V3": RouterSpec(n_routed_experts=256, experts_per_token=8,
                              n_group=8, topk_group=4, n_shared_experts=1,
                              scoring="sigmoid", hidden_size=7168,
                              moe_layers=58),
}


def route_tokens(spec: RouterSpec, tokens: int, layers: int,
                 seed: int) -> np.ndarray:
    """(layers, tokens, experts_per_token) routed expert ids of a
    decode step, drawn from ``seed``.

    ``SeedSequence(seed)`` spawns ``layers + 1`` streams. Each token
    belongs to one of :data:`TOPICS` topics, drawn from a Zipf law of
    exponent 1 (``choice`` on the first stream). Layer ``l``'s logits
    are ``A_l[topic] + eps``, with ``A_l`` (topics x experts) and ``eps``
    (tokens x experts) standard normal float64 drawn in that order from
    stream ``l + 1``: a random router acting on clustered hidden states,
    drawn in distribution rather than through a ``hidden_size``-wide
    matmul. Scores are ``sigmoid(logit)`` with the selection bias at 0.
    Ties go to the lower group or expert index. Each token's experts come
    out in ascending id order. Layers are routed in parallel threads
    (NumPy releases the GIL in the draws and the array passes).
    """
    if spec.scoring != "sigmoid":
        raise ValueError(f"unknown scoring {spec.scoring!r}")
    streams = np.random.SeedSequence(seed).spawn(layers + 1)
    w = 1.0 / np.arange(1, TOPICS + 1)
    topic = np.random.default_rng(streams[0]).choice(TOPICS, size=tokens,
                                                     p=w / w.sum())
    with ThreadPoolExecutor() as pool:
        return np.stack(list(pool.map(
            lambda ss: _route_layer(spec, topic, np.random.default_rng(ss)),
            streams[1:])))


def _route_layer(spec: RouterSpec, topic: np.ndarray,
                 rng: np.random.Generator) -> np.ndarray:
    """(tokens, experts_per_token): one layer of :func:`route_tokens`."""
    E, G, K = spec.n_routed_experts, spec.n_group, spec.experts_per_token
    W = E // G
    tokens = len(topic)
    rows = np.arange(tokens)[:, None]
    a = rng.standard_normal((TOPICS, E))
    s = rng.standard_normal((tokens, E))
    # s = sigmoid(a[topic] + eps), in place
    s += a[topic]
    np.negative(s, out=s)
    np.exp(s, out=s)
    s += 1.0
    np.divide(1.0, s, out=s)
    sg = s.reshape(tokens, G, W)
    top2 = np.partition(sg, W - 2, axis=2)[:, :, W - 2:]
    groups = np.sort(np.argsort(-(top2[:, :, 1] + top2[:, :, 0]),
                                axis=1, kind="stable")
                     [:, :spec.topk_group], axis=1)
    # the kept groups' scores, in expert order
    cand = sg[rows, groups].reshape(tokens, -1)
    C = cand.shape[1]
    kth = np.partition(cand, C - K, axis=1)[:, C - K:C - K + 1]
    take = cand >= kth
    tied = np.nonzero(take.sum(axis=1) != K)[0]
    if len(tied):   # equal scores at the K-th place: lower ids first
        c, v = cand[tied], kth[tied]
        over, eq = c > v, c == v
        take[tied] = over | (eq & (np.cumsum(eq, axis=1)
                                   <= K - over.sum(axis=1, keepdims=True)))
    j = np.nonzero(take)[1].reshape(tokens, K)
    return np.take_along_axis(groups, j // W, axis=1) * W + j % W


def ep_copies(experts: np.ndarray, tokens_per_chip: int, n: int,
              experts_per_chip: int) -> np.ndarray:
    """(layers, n, n) int64 token copies from chip to chip: one per
    (token, distinct destination chip other than its own), expert ``e``
    living on chip ``e // experts_per_chip``. Two experts on one chip
    take one copy; an expert on the token's own chip takes none."""
    L, T, _ = experts.shape
    src = np.arange(T) // tokens_per_chip
    out = np.zeros((L, n, n), np.int64)
    for layer in range(L):
        d = np.sort(experts[layer] // experts_per_chip, axis=1)
        keep = d != src[:, None]
        keep[:, 1:] &= d[:, 1:] != d[:, :-1]
        pair = np.broadcast_to(src[:, None], d.shape)[keep] * n + d[keep]
        out[layer] = np.bincount(pair, minlength=n * n).reshape(n, n)
    return out


def ep_decode_trace(pod, arch, *, tokens_per_chip: int, layers: int,
                    period: int, seed: int,
                    experts_per_chip: int) -> PhasedTraffic:
    """One decode step of an expert-parallel MoE pool as a phased
    schedule: dispatch of layer 1, combine of layer 1, ..., combine of
    layer ``layers``.

    ``pod`` is a :class:`~repro.core.topology.Pod` or its dims, chips
    numbered ``x + X*y + X*Y*z``; ``arch`` a :data:`ROUTERS` name or a
    :class:`RouterSpec`. Every chip holds ``tokens_per_chip`` tokens
    (token ``t`` on chip ``t // tokens_per_chip``) and
    ``experts_per_chip`` routed experts (expert ``e`` on chip
    ``e // experts_per_chip``); attention and shared experts stay
    local. Tokens are routed by :func:`route_tokens`. A dispatch phase's demand is the layer's
    :func:`ep_copies` times :attr:`RouterSpec.dispatch_bytes`, its
    combine phase the copies transposed times
    :attr:`RouterSpec.combine_bytes`. A phase's source rates are
    ``float32(row_bytes * n / phase_bytes)`` (mean 1) and its cycles
    ``period * phase_bytes // step_bytes``, at least 1, the last phase
    taking the rest of ``period``: each float the simulator reads
    follows from integer byte counts.

    The call is the program span ``workload.ep_trace``, with the stages
    ``.route`` and ``.matrix``, and counts, over all layers,
    ``workload.ep.copies`` (dispatch copies), ``.cross_cube_copies``,
    ``.max_in_copies`` (the most copies one chip receives in one
    dispatch phase) and ``.mean_in_copies`` (copies per chip and
    dispatch phase, rounded down).
    """
    spec = ROUTERS[arch] if isinstance(arch, str) else arch
    dims = tuple(pod.dims if isinstance(pod, Pod) else pod)
    n = int(np.prod(dims))
    if experts_per_chip * n != spec.n_routed_experts:
        raise ValueError(f"{spec.n_routed_experts} experts do not fill "
                         f"{n} chips at {experts_per_chip} per chip")
    if not 1 <= layers <= spec.moe_layers:
        raise ValueError(f"layers must be in [1, {spec.moe_layers}]")
    with obs.span("workload.ep_trace"):
        with obs.span("workload.ep_trace.route"):
            experts = route_tokens(spec, n * tokens_per_chip, layers, seed)
        with obs.span("workload.ep_trace.matrix"):
            copies = ep_copies(experts, tokens_per_chip, n,
                               experts_per_chip)
            phases = []
            for c in copies:
                phases += [c * spec.dispatch_bytes,
                           c.T * spec.combine_bytes]
            totals = [int(b.sum()) for b in phases]
            step = sum(totals)
            if step == 0:
                raise ValueError("the step sends nothing between chips")
            cycles = [max(1, period * b // step) for b in totals[:-1]]
            cycles.append(period - sum(cycles))
            if cycles[-1] < 1:
                raise ValueError("period too short for the phases")
            patterns = tuple(
                TrafficPattern.from_matrix(
                    f"{'dispatch' if p % 2 == 0 else 'combine'}{p // 2}",
                    b.astype(np.float64),
                    src_rate=(b.sum(axis=1) * n / max(t, 1))
                    .astype(np.float32))
                for p, (b, t) in enumerate(zip(phases, totals)))
            i = np.arange(n)
            X, Y, _ = dims
            cube = ((i % X) // CUBE, (i // X % Y) // CUBE,
                    (i // (X * Y)) // CUBE)
            cross = np.zeros((n, n), bool)
            for axis in cube:
                cross |= axis[:, None] != axis[None, :]
            obs.count("workload.ep.copies", copies.sum())
            obs.count("workload.ep.cross_cube_copies",
                      copies[:, cross].sum())
            obs.count("workload.ep.max_in_copies", copies.sum(axis=1).max())
            obs.count("workload.ep.mean_in_copies",
                      copies.sum() // (n * layers))
    return PhasedTraffic(f"ep-decode-{seed}", patterns, tuple(cycles))


def demand_pair_weight(wd: WorkloadDemand, cap: int = 64) -> np.ndarray:
    """Quantize a demand matrix into the integer multiplicities that
    :func:`repro.core.routing.select_paths` consumes as ``pair_weight``:
    the smallest positive weight maps to 1, heavier pairs to their
    (capped) integer ratio. Zero-weight pairs still route at weight 1
    (every pair keeps a path; only the balance objective changes).
    """
    m = wd.matrix()
    pos = m[m > 0]
    if pos.size == 0:
        return np.ones_like(m)
    return np.clip(np.rint(m / pos.min()), 1, cap)


def evaluate_workload(topo, wd: WorkloadDemand,
                      trace: Optional[PhasedTraffic] = None,
                      cfg: Optional[PipelineConfig] = None,
                      sat_kwargs: Optional[dict] = None,
                      weighted_routing: bool = True) -> dict:
    """Score one topology on one workload: demand-weighted MCF (exact
    LP) + trace-replay saturation (simulated), via the routing facade.

    ``weighted_routing`` (default) routes with the demand's integer
    pair multiplicities so path selection balances the *workload's*
    channel load, not the uniform proxy -- the co-design applies to
    routing as well as synthesis. It forces the array engine (the
    weighted one); pass ``weighted_routing=False`` to score with the
    demand-blind pipeline exactly as the other benchmarks run it.
    """
    from repro.core.netsim import saturation_point
    out: dict = {"name": topo.name, "n": topo.n}
    out["weighted_mcf"] = float(weighted_mcf(topo, wd))
    cfg = cfg or PipelineConfig()
    pw = None
    if weighted_routing:
        pw = demand_pair_weight(wd)
        if cfg.engine != "array":
            cfg = dataclasses.replace(cfg, engine="array")
    rp = route_pod(topo, cfg, pair_weight=pw)
    out["l_max"] = rp.l_max
    sat, _ = saturation_point(rp.tables,
                              traffic=trace or replay_trace(wd),
                              **(sat_kwargs or {}))
    out["trace_saturation"] = float(sat)
    return out


def workload_tenant(name: str, podspec, nodes: Sequence[int], arch: str,
                    shape: str = "train_4k",
                    rate_share: float = 1.0) -> TenantSpec:
    """One job's sub-pod slice as a tenant: the workload's full-pod
    demand matrix restricted to ``nodes`` (a job placed on a cube keeps
    its in-cube TP/EP weights; a job spanning cubes keeps its rings).
    Compose several with :func:`repro.core.traffic.compose_tenants`.
    """
    wd = workload_demand(podspec, arch, shape)
    nodes = np.asarray(nodes, np.int64)
    sub = wd.matrix()[np.ix_(nodes, nodes)]
    return TenantSpec(name, nodes, sub, rate_share)
