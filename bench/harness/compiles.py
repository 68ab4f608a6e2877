"""Counts XLA compiles and persistent-cache loads through ``jax.monitoring``.

JAX reports ``backend_compile_duration`` for every program it builds, loaded
from the persistent cache or compiled; a cache load also reports a
``cache_hits`` event.  So compiles proper are the first count less the
second.  The solver compiles window rungs ahead in background threads, hence
the lock.
"""

from __future__ import annotations

import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self) -> None:
        self.builds = 0  # programs built: compiled or loaded from the cache
        self.cache_hits = 0
        self._lock = threading.Lock()

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.builds += 1

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple:
        """``(programs built, of which loaded from the cache)``."""
        with self._lock:
            return self.builds, self.cache_hits

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self
