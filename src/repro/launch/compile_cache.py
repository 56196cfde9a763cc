"""Where JAX keeps its persistent compilation cache.

A cold serving run compiles a few hundred small programs (one per
stream-pool width x GP dataset bucket x admission size), so repeated
runs on the same machine should find them again. Entry points call
:func:`place_compile_cache` first thing in ``main``; importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# a fixed path inside the checkout: the cache directory is part of the
# lookup, so a path built from a tmp name, a pid or the time never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Return the compile-cache directory this process uses.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
    directory is left alone; otherwise the cache goes to
    ``<checkout>/.jax_cache``. Either way every program is cached,
    however fast it compiled: most serving programs compile in well
    under JAX's default one-second threshold, and together they are most
    of a cold run's set-up time."""
    import jax
    dirname = os.environ.get(ENV_VAR)
    if not dirname:
        dirname = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", dirname)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return dirname
