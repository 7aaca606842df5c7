"""Count XLA backend compiles and persistent-cache traffic from JAX's own
monitoring events (copied from chip_smoke._watch_compiles; the original is
listed in PERF.md's open questions for a later PR to fold in)."""

from __future__ import annotations

import time

__all__ = ["CompileWatch"]


class CompileWatch:
    """Every backend compile (a persistent-cache hit shows up as a cache
    hit and costs only its retrieval) with the host-clock time it ended
    at, so that a build inside the timed phase can be told apart."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.builds: list[tuple[float, str, float]] = []  # (t, event, s)
        self._installed = False

    def install(self) -> "CompileWatch":
        if self._installed:
            return self
        import jax.monitoring

        def on_duration(event: str, seconds: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += seconds
                self.builds.append((time.perf_counter(), "compile", seconds))
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.builds.append((time.perf_counter(), "cache_load",
                                    seconds))

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        self._installed = True
        return self

    def builds_between(self, start_s: float, end_s: float) -> int:
        """Programs built (compiled or loaded from the cache) with their
        end inside [start_s, end_s] on the host clock."""
        return sum(1 for t, _e, _s in self.builds if start_s <= t <= end_s)
