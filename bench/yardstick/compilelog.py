"""Backend compilations and persistent-cache hits, from JAX's monitoring
events."""
from __future__ import annotations

import threading
import time

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Counts backend compilations (with their durations and the clock at
    which each ended) and persistent-cache hits and misses."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events: list = []  # (end time on perf_counter, seconds, name)
        self.hits = self.misses = 0

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_kw):
        if event == BACKEND_COMPILE:
            with self._lock:
                self.events.append((time.perf_counter(), float(duration), fun_name))

    def _event(self, event, **_kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def between(self, t0: float, t1: float) -> list:
        """Compilations that ended in [t0, t1] on the perf_counter clock."""
        with self._lock:
            return [e for e in self.events if t0 <= e[0] <= t1]
