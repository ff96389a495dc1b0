"""Compiles and persistent-cache hits, from JAX's monitoring events."""
from __future__ import annotations

import threading


class CompileMeter:
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.seconds, "compiles": self.compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}
