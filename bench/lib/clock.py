"""Compile seconds and persistent-cache hits, from JAX's monitoring events."""
from __future__ import annotations

import jax


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache reads
    included), the number of such compiles, and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def install(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}
