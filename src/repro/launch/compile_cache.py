"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``serve``, ``chip_smoke.py``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the cache
goes there — nothing here sets another directory. Otherwise the cache goes
to one fixed, git-ignored directory inside the checkout. The path is part
of what a cached entry is found by, so it never depends on a temp dir, a
pid or the time. Called explicitly by entry points, never at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns
    the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
