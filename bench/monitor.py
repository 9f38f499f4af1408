"""Counter of compilations, from JAX's own monitoring events.

JAX records ``/jax/core/compile/backend_compile_duration`` each time it
obtains an executable for a new program, whether by compiling it or by
reading it from the persistent cache.  Inside the measured window that
count should be 0.
"""
from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts compile events while started; one listener per instance."""

    def __init__(self):
        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_secs: float, **kwargs) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1

    def start(self) -> None:
        self.count = 0
        self.active = True

    def stop(self) -> int:
        self.active = False
        return self.count


class CacheCounter:
    """Counts the persistent compilation cache's hits and misses."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
