"""Counts XLA programs compiled, with the time each ended, from JAX's
monitoring events, so a compile inside the window is reported and not
hidden. JAX records ``backend_compile_duration`` around every compile,
one served from the persistent compilation cache included; a
``cache_hits`` event marks the ones that were."""
from __future__ import annotations

import threading
import time
from typing import List

import jax

COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        self.times: List[float] = []
        self.hits: List[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE:
            with self._lock:
                self.times.append(time.monotonic())

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            with self._lock:
                self.hits.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        """Programs compiled, or loaded from the cache, in ``(t0, t1]``."""
        with self._lock:
            return sum(t0 < t <= t1 for t in self.times)

    def total(self) -> int:
        with self._lock:
            return len(self.times)

    def cache_hits(self) -> int:
        with self._lock:
            return len(self.hits)
