"""Where compiled XLA programs persist between processes.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call `use_compile_cache` once at start-up; library code never does, so
importing any module of this package leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — fixed, since the directory is part of what a
# later process has to find (listed in .gitignore)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache is the checkout's
    ``.jax_cache``.  -> the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
