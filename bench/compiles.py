"""Counts of executables JAX builds, from its monitoring events.

JAX fires ``/jax/core/compile/backend_compile_duration`` around every
executable it builds for a call that found none in the process, whether it
compiles it or takes it from the persistent cache; a cache hit also fires
``/jax/compilation_cache/cache_retrieval_time_sec``.
"""
from __future__ import annotations

import threading

COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileClock:
    def __init__(self, jax):
        self._lock = threading.Lock()
        self.built = 0          # compiled or loaded from the cache
        self.cache_loads = 0
        self.build_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        with self._lock:
            if event == COMPILE:
                self.built += 1
                self.build_s += duration
            elif event == CACHE_LOAD:
                self.cache_loads += 1

    @property
    def compiled(self) -> int:
        """Executables compiled, not found in the cache."""
        return self.built - self.cache_loads
