"""JAX's persistent compilation cache, for the entry points.

A cold run on a chip compiles every step program it meets (the fused cycle
compiles once per layer group and partition entry), so later processes of
the same checkout should find them again. Entry points call
``enable_compile_cache`` once at start-up; tests never do.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the fallback cache directory: fixed and inside the checkout, since the
#: path is part of what a later process must match to hit the cache
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing else is set; otherwise the cache goes to
    ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
