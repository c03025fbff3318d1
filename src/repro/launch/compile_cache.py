"""JAX persistent compilation cache placement.

A cache entry is keyed on, among other things, the cache directory, so a
directory that moves between runs never hits.  Entry points call
:func:`enable_compile_cache` before their first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
    is changed — whoever runs the program places the cache;
  * otherwise the cache goes to the fixed ``<checkout>/.jax_cache``
    (gitignored), never to a temporary, per-process or timestamped name.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(checkout) -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` when set, else at ``<checkout>/.jax_cache``; returns the
    directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    path = str(Path(checkout).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
