"""Where XLA's persistent compilation cache lives.

Called by the entry points only (``cli.main``'s trainer branch,
``chip_smoke.py``, ``examples/wrn_accuracy.py``) before their
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
    variable itself and this sets no directory.  Without it the cache sits at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of what a cache entry is keyed on and a directory that moves never
    hits.
    """
    # JAX leaves the ops' metadata (``jax.named_scope`` paths, source
    # lines) out of an entry's key by default, so an executable compiled
    # before a scope was named is served after it, and a profile shows the
    # old names.  The names are what the program's profiles are read by
    # (docs/observability.md), so entries are keyed on them too.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
