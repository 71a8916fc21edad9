"""JAX's persistent compilation cache for the repo's entry points.

Called by scripts (``chip_smoke.py``, ``benchmarks.run``,
``benchmarks.sim_throughput``) before their first compile, never at
library import.
"""
from __future__ import annotations

import os
from pathlib import Path


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there
    and nothing is changed. Otherwise the cache lives in
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    the cache key and a directory that moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    checkout = Path(__file__).resolve().parents[2]  # <checkout>/src/repro
    path = str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
