"""JAX's persistent compilation cache for the command-line entry points.

A cold chip run compiles every kernel and step program again; the cache
keeps the compiled executables on disk so a second run in the same
checkout reuses them. The directory is part of the cache's key, so it is a
fixed path — never a temp, pid- or time-based name. Importing ``repro``
does not touch the cache: only the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``bench/run.py``) call :func:`enable_compile_cache`.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/cache.py)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it: it is
    left alone and no other directory is set. Otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
