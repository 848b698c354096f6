"""Compile seconds and cache hits, from JAX's own monitoring events.

A copy of ``chip_smoke.py``'s ``CompileMeter`` (sound: the programs under
test are not touched), kept here so that no later PR can change what
``setup_s`` and ``compile_s`` are made of.  ``events`` counts backend
compiles and cache loads alike: after warm-up it must stand still.
"""

from __future__ import annotations

import jax


class CompileMeter:
    def __init__(self):
        self.compile_s = 0.0
        self.events = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.events += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
