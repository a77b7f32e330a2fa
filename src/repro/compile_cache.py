"""JAX's persistent compile cache, kept in one fixed place.

The cache key includes the directory, so a directory that moves between
runs never hits. Scripts that compile for the chip call
:func:`configure_compile_cache` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: Used when the environment names no cache: inside the checkout, gitignored.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Return the compile-cache directory, pointing JAX at it if needed.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and nothing
    here overrides it. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
