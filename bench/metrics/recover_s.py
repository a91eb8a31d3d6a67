"""recover_s: wall seconds per OCS repair, the whole window over the
repairs in it."""
from bench.harness import per_unit as read  # noqa: F401
