"""Where XLA's persistent compilation cache lives.

Called by the entry points only (``cli.main``'s trainer branch,
``bench.py``, ``benchmarks/common.py``, ``chip_smoke.py``) before their
first compile — never at library import and never by the tests, so
importing the package changes no JAX configuration.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places it from outside: JAX reads that
    variable itself and this sets nothing.  Without it the cache sits at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of what a cache entry is keyed on and a directory that moves never
    hits.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
