"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at a fixed
``<checkout>/.jax_cache``: the directory is part of every entry's key, so
a path built from a temporary name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``src/repro/launch/`` sits three levels below the checkout root
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Returns the directory compiled programs are written to.
    """
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
