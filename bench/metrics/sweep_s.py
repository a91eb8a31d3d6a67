"""sweep_s: wall seconds per sweep, the whole window over the sweeps in it."""
from bench.harness import per_unit as read  # noqa: F401
