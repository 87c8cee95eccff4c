"""JAX's persistent compilation cache for the entry scripts.

A cache hits only where its directory stays put, so it lives at one fixed
path inside the checkout (``.jax_cache``, git-ignored).  Where the
environment names a directory in ``JAX_COMPILATION_CACHE_DIR``, JAX reads
that itself and nothing is set here.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
