"""Backend compiles and persistent-cache loads that JAX reports.

``backend_compile_duration`` fires whenever an executable is obtained
for a new shape, whether compiled or loaded from the persistent cache,
so its count says how many programs a phase needed.  The time spans of
each program's tracing, lowering and compile or load are kept too, so
``busy`` can say how much of a stretch of time went into them, however
many threads compiled at once.
"""

from __future__ import annotations

import threading

_STAGES = ("/jax/core/compile/jaxpr_trace_duration",
           "/jax/core/compile/jaxpr_to_mlir_module_duration",
           "/jax/core/compile/backend_compile_duration")


class CompileLog:
    def __init__(self, jax):
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.by_name: dict[str, int] = {}
        self._spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_):
        if event == _STAGES[-1]:
            with self._lock:
                self.count += 1
                self.seconds += duration
                self.by_name[fun_name] = self.by_name.get(fun_name, 0) + 1

    def _span(self, event, start, end, **_):
        if event in _STAGES:
            with self._lock:
                self._spans.append((start, end))

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def busy(self, t0: float, t1: float) -> float:
        """Seconds of ``[t0, t1]`` (``time.time()``) in which some
        program was being traced, lowered, compiled or loaded."""
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1)) for a, b in self._spans
                           if b > t0 and a < t1)
        total, end = 0.0, t0
        for a, b in spans:
            if b > end:
                total += b - max(a, end)
                end = b
        return total

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.count, "seconds": self.seconds,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses,
                    "by_name": dict(self.by_name)}
