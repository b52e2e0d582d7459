"""Persistent XLA compilation cache, on for every process.

Q1-class programs cost seconds of XLA compile per process even after the
carry-save lowering, and a serving process compiles every relation
program it dispatches; a persistent on-disk cache makes every process
after the first start warm.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it natively and
this module sets no other directory. Otherwise the cache lives at
``<checkout>/.jax_cache``, derived from this file's location and never
from a temp name, pid or time: a cache is only hit where the next
process finds it again, so every process run from one checkout (test
workers, ``chip_smoke.py``, the benchmarks) shares it. Delete the
directory to time a cold start.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at ``DEFAULT_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one. Returns the
    directory in use. Safe to call repeatedly and before any jit."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    # JAX creates the directory on its first write.
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


def cache_dir() -> Optional[str]:
    """The directory JAX's persistent cache uses now (None when off)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def n_entries() -> int:
    """Executables in the cache directory now (0 when it is off)."""
    d = cache_dir()
    if not d or not os.path.isdir(d):
        return 0
    return sum(1 for f in os.listdir(d) if f.endswith("-cache"))
