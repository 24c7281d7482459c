"""JAX persistent compilation cache, placed from outside the program.

Entry points (the ``launch`` CLIs, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up,
before their first compile; nothing calls it at import. The directory
is part of each cache entry's identity, so it never moves:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  this module sets no other directory;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored),
  never at a path built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
