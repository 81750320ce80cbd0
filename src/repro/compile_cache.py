"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` before they
compile anything: the cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when
that is set, else in a fixed directory the caller names.  The path is
part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os


def use_compile_cache(default_dir: str) -> str:
    """Point JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or,
    when unset, ``default_dir``; returns the directory used."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_dir
    jax.config.update("jax_compilation_cache_dir", path)
    return path
