"""Where the persistent XLA compile cache lives — decided in ONE place.

Every entry point that compiles (the ``fleet`` roles, ``bench.py --child``,
``chip_smoke.py``'s phases, ``tests/conftest.py``) calls
:func:`enable_compile_cache` before its first dispatch. The directory is
part of the cache key's neighbourhood: a directory that moves never hits,
so it is never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` set from outside always wins: JAX reads
    the variable itself, so nothing is set in code and child processes
    inherit it untouched. Otherwise the cache is ``<checkout>/.jax_cache``.
    Must run before the process's first compilation (JAX opens the cache
    once)."""
    outer = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outer:
        return outer
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
