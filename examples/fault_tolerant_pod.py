"""Fault-tolerant pod walkthrough: synthesize with the C8 fault budget,
build robust routing, knock out an OCS, and show the job keeps running --
the network-level story (TONS robust routing) plus the framework-level
story (checkpoint restore after a preemption).

Run:  PYTHONPATH=src python examples/fault_tolerant_pod.py
"""
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import numpy as np

from repro.core import fault as F, topology as T
from repro.core.mcf import mcf_topology
from repro.core.pipeline import PipelineConfig, route_pod


def main() -> None:
    # --- network side -----------------------------------------------------
    print("== robust TONS fabric under a single-OCS fault ==")
    import pickle
    pk = Path(__file__).parent.parent / "benchmarks/results/tons_128.pkl"
    if pk.exists():
        d = pickle.load(open(pk, "rb"))
        topo = T.Topology(T.Pod((4, 4, 8)),
                          [tuple(e) for e in d["optical"]], name="TONS 128")
        lam = d["mcf"]
    else:
        topo = T.pdtt((4, 4, 8))
        lam = 0.01364
    cert = F.fault_tolerance_certificate(topo, lam, f=1)
    print(f"C8 certificate: lambda={lam:.5f} >= "
          f"{cert['required_lambda']:.5f} -> up to "
          f"{cert['certified_f']} OCS faults tolerable "
          f"(color budget {cert['color_budget']})")

    cfg = PipelineConfig(robust=True, K=4, engine="array",
                         local_search_rounds=2, vc="none")
    rp = route_pod(topo, cfg)
    at, base = rp.at, rp.routed
    print(f"no fault: all pairs routed, L_max={base.l_max:.0f}")

    colors = F.colors_in_use(topo)
    fault = colors[len(colors) // 2]
    dead = F.dead_channels_for_color(at, fault)
    routed = route_pod(topo, cfg, at=at, dead_channels=dead).routed
    print(f"OCS {fault} failed ({len(dead)} channels dead): "
          f"unreachable={routed.unreachable}, L_max={routed.l_max:.0f} "
          f"({routed.l_max / base.l_max:.2f}x degradation)")
    assert routed.unreachable == 0

    # online repair: the serving fabric patches itself instead of
    # recomputing -- only the flows crossing dead channels re-route
    import time
    from repro.core.repair import ServingState, repair_fault
    t0 = time.time()
    st = ServingState.build(topo, n_vc=2, K=4, robust=True)
    t_build = time.time() - t0
    t0 = time.time()
    rr = repair_fault(st, dead)
    t_rep = time.time() - t0
    assert rr.unreachable == 0 and rr.deadlock_free
    print(f"online repair: {rr.flows_rerouted} of "
          f"{st.table.n_flows} flows re-routed in {t_rep:.2f}s "
          f"(cold build {t_build:.1f}s, "
          f"{t_build / max(t_rep, 1e-9):.0f}x), "
          f"L_max={rr.l_max:.0f}, deadlock-free")

    # simulate the degraded fabric under several traffic patterns: one
    # vmapped kernel serves them all, only the alias tables change
    from repro.core import netsim as NS
    from repro.core.demand import WorkloadDemand
    from repro.core.traffic import TrafficPattern
    tab = NS.at_tables(topo, at, routed)
    wd = WorkloadDemand(topo.pod, w_same_cube=2.0, w_ring=2.0,
                        w_uniform=0.25)
    patterns = [TrafficPattern.uniform(topo.n),
                TrafficPattern.transpose(topo.pod),
                TrafficPattern.hotspot(topo.n, [0, 1, 2, 3], 0.4),
                TrafficPattern.from_demand(wd)]
    for pat in patterns:
        r = NS.run(tab, 0.05, traffic=pat, cycles=1200, warmup=400)
        print(f"  {pat.name:10s}: delivered {r['delivered']:.4f} "
              f"of offered {r['offered']:.4f} under the fault")

    # --- framework side ----------------------------------------------------
    print("== training survives preemption via checkpoint restore ==")
    from repro.configs.registry import get_config
    from repro.data.synthetic import DataConfig
    from repro.optim.adamw import OptConfig
    from repro.train.loop import TrainConfig, Trainer
    cfg = get_config("qwen2.5-3b").smoke_model()
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(steps=6, ckpt_every=3, ckpt_dir=d, log_every=3)
        t1 = Trainer(cfg, DataConfig(vocab=cfg.vocab, seq_len=32,
                                     global_batch=4),
                     OptConfig(total_steps=6), tc)
        t1.run()
        # "preemption": a fresh process picks up from the last checkpoint
        t2 = Trainer(cfg, DataConfig(vocab=cfg.vocab, seq_len=32,
                                     global_batch=4),
                     OptConfig(total_steps=6),
                     TrainConfig(steps=8, ckpt_every=3, ckpt_dir=d,
                                 log_every=3))
        print(f"restarted at step {t2.start_step}")
        out = t2.run()
        assert out["final_step"] == 8
    print("ok: fabric re-routed and training resumed")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
