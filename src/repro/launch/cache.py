"""JAX's persistent compilation cache, configured in one place.

Entry points (``chip_smoke.py``, ``python -m repro.compress``, the
examples) call :func:`enable_compile_cache` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing else is configured here.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout: the path is part of what makes a cache
hit, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
