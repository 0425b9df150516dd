"""JAX's persistent compilation cache, kept in one fixed place.

The cache key includes the directory, so a directory named from a
temporary path, a pid or the time would never hit.  Entry points (the
chip smoke run, ``repro.launch.serve``, ``repro.launch.train``) call
:func:`enable_compile_cache` once at start-up; importing this module does
nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: set by the environment, this directory wins and nothing else is set
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when the environment names none
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on; returns its directory.

    JAX reads ``$JAX_COMPILATION_CACHE_DIR`` itself, so when it is set
    this only reports it.  Otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env)
    import jax

    DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR
