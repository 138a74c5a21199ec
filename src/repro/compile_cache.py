"""Where the entry points keep JAX's persistent compilation cache.

A cold TPU process recompiles every kernel and jitted engine; the
persistent cache lets a later process of the same checkout skip that.  The
cache key includes the directory, so it must not move: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
overrides it; otherwise the cache goes to one fixed directory inside the
checkout (``.jax_cache/``, gitignored).

Called from ``main`` functions only — importing a library module must not
change where a caller's process caches.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "use_compile_cache"]

#: the in-checkout default (``<checkout>/.jax_cache``)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
