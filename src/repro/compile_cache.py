"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call ``enable_compile_cache()`` once, before their first compile; tests
never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, because the path is part of what a later run must find.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    path = REPO / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
