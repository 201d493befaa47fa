"""Where JAX's persistent compilation cache lives.

The cache is keyed by its path, so the path is fixed: the directory named
by ``JAX_COMPILATION_CACHE_DIR`` when that variable is set (JAX reads it
itself), otherwise ``.jax_cache/`` at the root of the checkout.  Call
`enable_compile_cache` before the first compile.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
