"""JAX's persistent compilation cache, placed from outside.

Entry points (``launch/train.py``, ``chip_smoke.py``, ``benchmarks/run.py``)
call ``enable_compilation_cache()`` from ``main()``; importing this module
changes nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no other directory is set. Otherwise the cache is the fixed
``<repo>/.jax_cache``: the path is part of what a later run must find, so
it never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
