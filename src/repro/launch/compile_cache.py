"""JAX's persistent compilation cache at a place that does not move.

The cache key includes the cache's path, so a directory that changes from
run to run never hits. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it by itself and nothing here overrides it; otherwise the cache is
``<checkout>/.jax_cache`` (listed in ``.gitignore``). Entry points call
:func:`use_compile_cache` first thing in ``main``; importing this module
changes nothing.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Make sure the persistent compilation cache is on; return its path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
