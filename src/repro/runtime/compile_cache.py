"""JAX's persistent compilation cache, kept in one fixed place.

A full-width step program compiles for tens of seconds per shape bucket, and
every fresh process on a chip machine starts cold.  Entry points call
``enable_compile_cache()`` first thing in ``main()`` — never on import — so
that a second process finds what the first compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax
from jax.experimental.compilation_cache import compilation_cache

# src/repro/runtime/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and no other is
    set.  Otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
    ``.gitignore``).  The path is fixed — never built from a temporary name,
    a pid or the time — because a directory that moves never hits.  Every
    program is cached, however quickly it compiled: the serve loop's many
    small bucket programs add up to minutes on a cold start.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # a process that compiled before this call has already decided the
        # cache is off: make it look again at its next compile
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
