"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, the benchmarks, the examples) call
`use_compile_cache` once before their first compile, so a process that
compiles a launch shape an earlier process already compiled loads it
instead.  The cache key includes the directory, so the path is fixed:
never built from a temp name, a PID or the time.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache — this file is <checkout>/src/repro/utils/...
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory
    itself and nothing is changed here.  Otherwise the cache goes to
    `CACHE_DIR`, inside the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
