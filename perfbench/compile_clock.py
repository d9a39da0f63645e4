"""Seconds of XLA compilation and persistent-cache hits in this process,
summed from JAX's own monitoring events (a cache hit's compile event lasts
only as long as the read)."""

from __future__ import annotations

import threading

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        self.hits = 0
        self._lock = threading.Lock()   # compiles also run on other threads
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.seconds += duration_secs
                self.events += 1

    def _event(self, event: str, **_) -> None:
        if event == HIT_EVENT:
            with self._lock:
                self.hits += 1

    def read(self) -> tuple[float, int, int]:
        with self._lock:
            return self.seconds, self.events, self.hits
