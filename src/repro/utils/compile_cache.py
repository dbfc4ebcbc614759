"""JAX's persistent compilation cache, placed from outside the program.

The cache directory is part of what lets a later process find a
compiled program again, so it never moves between runs: where
`JAX_COMPILATION_CACHE_DIR` is set, that directory is used (JAX reads
the variable itself); otherwise `.jax_cache` at the root of the
checkout, which git ignores."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
