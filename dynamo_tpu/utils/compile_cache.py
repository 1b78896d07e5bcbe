"""Where this checkout keeps JAX's persistent compilation cache.

Called once from each process entry point under which an ``EngineCore``
is built (launch/run.py, sdk/serve_worker.py, bench.py, tools/*_bench.py,
tools/decode_profile.py, chip_smoke.py) — never at import. The directory
is part of the cache key's lookup, so it is one fixed path inside the
checkout (git-ignored), never a temp name, pid or timestamp; a fresh
machine starts cold and every later process of the same checkout hits.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "CACHE_DIR"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    ``JAX_COMPILATION_CACHE_DIR`` placed from outside wins: JAX reads the
    variable itself, so then this sets nothing."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
