"""JAX's persistent compilation cache, at one fixed place.

Every entry point (``launch/train.py``, ``chip_smoke.py``,
``benchmarks/kernel_bench.py``, the examples) calls
``enable_compile_cache()`` before it compiles anything. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
directory stands. Otherwise the cache goes to ``<checkout>/.jax_cache``
(git-ignored): a fixed path, so a later run of the same checkout finds
what an earlier one compiled — the path is part of the cache's key.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the
    checkout's fixed one."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
