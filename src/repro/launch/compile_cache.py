"""Where JAX's persistent compilation cache lives.

The cache key includes the cache directory, so a directory that moves
between runs never hits.  ``enable_compile_cache`` is called by the
launchers and ``chip_smoke.py`` at start-up (never at import).
"""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, so
    nothing is changed.  Otherwise the cache goes to ``.jax_cache`` at
    the root of this checkout: a fixed path, so the next run from the
    same checkout finds what this one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
