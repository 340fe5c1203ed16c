"""JAX's persistent compilation cache, placed from outside the program.

A chip run compiles the window engine cold unless a previous process left
its executables in the cache, and the cache only hits when its directory
stays put.  ``JAX_COMPILATION_CACHE_DIR``, when set, names that directory
and JAX reads it itself; otherwise the cache lives at a fixed ``.jax_cache/``
in the checkout (listed in ``.gitignore``).  The path is never derived from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (``src/repro/launch`` -> repo root)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it.  With ``JAX_COMPILATION_CACHE_DIR`` set nothing is changed."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
