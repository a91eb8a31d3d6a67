"""Fig. 7: cumulative all-to-all network throughput (TB/s), PT vs TONS.

Sustained aggregate throughput = simulated saturation rate x nodes x
flit-bytes x clock (Table 2: 128 B flits @ 1.05 GHz ~ one flit per link
per cycle = 128 GB/s links)."""
from __future__ import annotations

import argparse

from benchmarks.common import emit, load_tons, timed

FLIT_B = 128
CLOCK = 1.05e9


def agg_tbps(sat_per_node: float, n: int) -> float:
    return sat_per_node * n * FLIT_B * CLOCK / 1e12


def main(full: bool = False) -> None:
    from benchmarks.fig5_saturation import saturation
    from repro.core import topology as T
    from repro.core.traffic import TrafficPattern

    step = 0.04 if not full else 0.02
    pt = T.pt((4, 4, 8))
    # all-to-all == uniform demand over every ordered pair
    a2a = TrafficPattern.uniform(pt.n)
    sat_pt, us = timed(saturation, pt, "dor", step, 2500, 1000, 0, a2a)
    rows = [("PT+DOR", sat_pt)]
    loaded = load_tons(128)
    if loaded:
        sat_t, _ = timed(saturation, loaded[0], "at", step, 2500, 1000, 0,
                         a2a)
        rows.append(("TONS+AT", sat_t))
    print("# sustained a2a throughput at saturation (128 nodes)")
    for name, sat in rows:
        print(f"  {name:8s}: {agg_tbps(sat, 128):.2f} TB/s")
    if len(rows) == 2:
        gain = agg_tbps(rows[1][1], 128) - agg_tbps(rows[0][1], 128)
        print(f"  TONS gain: +{gain:.2f} TB/s "
              f"(paper: +9 TB/s at 256 nodes)")
        emit("fig7_gain_tbps", us, f"{gain:.2f}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(ap.parse_args().full)
