"""JAX's persistent compilation cache for the entry points.

A cold run on a chip recompiles the whole train step; with the cache on,
a later run of the same program on the same machine reads it back.
"""

from __future__ import annotations

import os
import pathlib

import jax

# one fixed path inside the checkout (git-ignored): the directory is part of
# what a later run has to find again, so it never depends on a temporary
# name, a process id or a time
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as the
    default of ``jax_compilation_cache_dir`` and this sets nothing;
    otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
