"""One place for JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``bench.py``, ``python -m parquet_tpu
serve``) call :func:`setup_compile_cache` once; the library never does at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
nothing here overrides it.  Otherwise the cache sits at one fixed path in
the checkout, ``<repo>/.jax_cache`` (gitignored): the path is part of the
cache key, so a temporary or per-process directory would never hit.
"""

from __future__ import annotations

import os

from .env import env_str

#: the fixed cache directory used when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Make sure JAX caches compiled programs persistently; returns the
    directory in use."""
    import jax

    d = env_str("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
