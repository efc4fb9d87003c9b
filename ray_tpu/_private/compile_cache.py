"""Where the persistent XLA compile cache goes.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
here names another directory. Where it is not, processes that compile for
the chip (train workers, serve replicas) share ``<checkout>/.jax_cache``: a
fixed path, because the path is part of how a later process finds what an
earlier one compiled — no temp name, pid or time in it.
"""

from __future__ import annotations

import os
import sys

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def place_compile_cache() -> str:
    """Return the cache directory in force, setting the default when the
    environment names none. Safe before or after ``import jax``."""
    cache_dir = os.environ.get(ENV_CACHE_DIR)
    if cache_dir:
        return cache_dir
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cache_dir = os.path.join(checkout, ".jax_cache")
    os.environ[ENV_CACHE_DIR] = cache_dir
    jax = sys.modules.get("jax")
    if jax is not None:  # jax read the (unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
