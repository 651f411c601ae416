"""Process-level runtime settings shared by the entry points."""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# the checkout root (src/repro/runtime_env.py -> ../..)
_CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         ".."))


def use_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, because the directory is part
    of what a later process must find again.  Entry points call this
    before their first compile; the tests do not.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
