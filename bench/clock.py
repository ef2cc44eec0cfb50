"""When, and for how long, XLA compiled; and whether the persistent
compilation cache served it.

JAX's monitoring events for backend compiles, each stamped with the time
it ended. Tracing and lowering events nest, so summing them would count
time twice; only the backend compile is counted. Cache hits and misses
are JAX's own counters of the persistent compilation cache.
"""

from __future__ import annotations

import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileClock:
    def __init__(self, monitoring):
        self.events = []
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), duration))

    def _on_event(self, event, **_):
        if event == HIT_EVENT:
            self.hits += 1
        elif event == MISS_EVENT:
            self.misses += 1

    def seconds(self, since: float, until: float = float("inf")) -> float:
        """Compile seconds of the compiles that ended in [since, until)."""
        return sum(d for t, d in self.events if since <= t < until)

    def count(self, since: float, until: float = float("inf")) -> int:
        return sum(1 for t, _ in self.events if since <= t < until)
