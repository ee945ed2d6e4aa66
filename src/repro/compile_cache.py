"""Where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` once at start-up (never at
import). A directory named by ``JAX_COMPILATION_CACHE_DIR`` is used as is —
JAX reads that variable itself — and no other is set. Without it the cache
goes to a fixed directory inside the checkout, ``.jax_cache/`` (git-ignored):
the path is part of what a later run must find again, so it never depends on
a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
