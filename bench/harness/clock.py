"""Compilations counted from JAX's own monitoring events (copied from the
repo's ``chip_smoke.py``): backend compile seconds and count, and
persistent-cache hits (a hit reports its retrieval instead of a compile).
Listeners cannot be removed, so one clock serves the whole process and a
window reads the difference of two snapshots."""

from __future__ import annotations

from typing import Optional


class CompileClock:
    _shared: Optional["CompileClock"] = None

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def shared(cls) -> "CompileClock":
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return (self.compiles, self.cache_hits, self.compile_s)
