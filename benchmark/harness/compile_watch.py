"""Counts what JAX itself reports about compiling: every trace, lowering
and backend compile (``/jax/core/compile/*`` durations) and the persistent
cache's hits and misses.  A statement during which none fires compiled
nothing.  Listeners cannot be removed, so one watch serves a process."""

from __future__ import annotations

import jax


class CompileWatch:
    def __init__(self):
        self._compile_events = 0
        self._cache = {"hits": 0, "misses": 0}
        self._seconds = {"backend": 0.0, "trace": 0.0, "lower": 0.0,
                         "cache_retrieval": 0.0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **_kw):
        if "/compile/" in name:
            self._compile_events += 1
        for stage, tail in (("backend", "/backend_compile_duration"),
                            ("trace", "/jaxpr_trace_duration"),
                            ("lower", "/jaxpr_to_mlir_module_duration"),
                            ("cache_retrieval", "/cache_retrieval_time_sec")):
            if name.endswith(tail):
                self._seconds[stage] += secs

    def _event(self, name: str, **_kw):
        if name.endswith("/cache_hits"):
            self._cache["hits"] += 1
        elif name.endswith("/cache_misses"):
            self._cache["misses"] += 1

    def count(self) -> int:
        return self._compile_events

    def cache_events(self) -> dict:
        return dict(self._cache)

    def seconds(self) -> dict:
        """JAX's own seconds so far, by stage.  A backend compile that the
        persistent cache answered is its retrieval, and counts in both."""
        return dict(self._seconds)
