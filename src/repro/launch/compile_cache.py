"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.  ``JAX_COMPILATION_CACHE_DIR``
places the cache from outside when it is set.  Otherwise the cache sits
at one fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored):
the directory is part of the cache key, so it is never built from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
