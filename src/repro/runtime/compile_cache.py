"""JAX's persistent compilation cache, set up the same way by every
entry point (chip_smoke.py, examples/, benchmarks/, the CLIs).

At the paper's parameters a cold process spends minutes compiling the
multiply, rotation and NTT programs; the cache lets later processes
reuse them.  Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself
and this sets no directory of its own.  Otherwise the cache lives in one
fixed directory inside the checkout (git-ignored): the path is part of
the cache key, so a directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_compile_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
