"""JAX's persistent compilation cache, set up once at process start.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` before their first compile, so a second
process on the same machine loads the full-width step programs instead of
compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Used when ``JAX_COMPILATION_CACHE_DIR`` is unset.  A fixed path inside the
#: checkout: a cache directory whose name changes between runs never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
