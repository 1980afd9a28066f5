"""JAX persistent compilation cache: one rule for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
code here sets a directory. Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (git-ignored): the path is part of the cache key,
so a directory that moves never hits.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in use."""
    import jax

    # the deep-scan kernels take seconds to minutes to compile; cache
    # anything that took at least a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
