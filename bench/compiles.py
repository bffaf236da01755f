"""Counts what jax hands its backend compiler (copied from ``chip_smoke.py``).

A persistent-cache hit still passes through the backend-compile event and is
counted apart; inside a measured window both must stay at zero.
"""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Compiles:
    def __init__(self):
        import jax.monitoring as mon

        self.n = self.hits = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1
            self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.hits += 1

    def mark(self) -> tuple:
        return self.n, self.hits, self.seconds

    def since(self, mark: tuple) -> dict:
        n, hits, seconds = mark
        return {"compiles": self.n - n, "cache_hits": self.hits - hits,
                "compile_seconds": self.seconds - seconds}
