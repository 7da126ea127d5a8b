"""JAX's persistent compilation cache for the entry points.

A later run finds a compiled program only in the directory that the run
which compiled it wrote to, so the directory never comes from a temp name,
a pid or the time:``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
itself, and nothing here overrides it), else ``.jax_cache`` at the root of
the checkout, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
