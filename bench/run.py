#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Progress, counts and compile events go to stdout as they happen; the
checks behind ``correct`` are the last lines on stderr; the last line on
stdout is the result object. Without the cell's TPU chips it exits 1 and
prints no result.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the repo root replaces this script's directory on the path, so the
# benchmark's modules are imported as the ``bench`` package and never
# shadow a standard module of the same name
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

# the reference draws its random bits on JAX's CPU backend, so keep it
# available when the platform list is pinned to the accelerator
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
