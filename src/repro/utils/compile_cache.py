"""JAX's persistent compilation cache, kept at one fixed place.

The cache directory is part of what makes a cached program findable, so it
must not move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (and no other directory is set in code), otherwise
``.jax_cache/`` at the root of the checkout — never a temporary, per-process
or per-run path.  Call :func:`enable_compile_cache` once at the top of an
entry point, before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where compiled programs are kept (see the module docstring)."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir`, for every
    program however quick its compile.  Returns the directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
