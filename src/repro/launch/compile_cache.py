"""Where the entry points keep JAX's persistent compilation cache.

The cache path is part of the cache key, so a cache only hits when its path
stays put.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
itself, and nothing here overrides it); otherwise the cache lives in one
fixed, gitignored directory inside the checkout, ``.jax_cache/``.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
IN_REPO = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    return os.environ.get(ENV) or str(IN_REPO)


def enable_compile_cache() -> str:
    """Point JAX at the cache directory (call before the first compile);
    returns the directory in use."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
