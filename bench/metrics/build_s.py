"""build_s: wall seconds per cold build, the whole window over the
builds in it."""
from bench.harness import per_unit as read  # noqa: F401
