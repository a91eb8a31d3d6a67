"""JAX's persistent compilation cache, placed for the entry points.

Scripts call :func:`use_compile_cache` once at start, before their first
compile; no library module calls it, so importing the library never
touches the cache. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX
reads it itself and nothing here overrides it. Otherwise the cache lives
at ``.jax_cache/`` in the checkout root, a fixed path, so every run of
the same checkout finds what the previous one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
