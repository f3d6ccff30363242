"""Low-overhead tracing: spans on ``time.perf_counter``, Chrome export.

The engine's request path is instrumented with ``obs.span(name, **attrs)``
context managers — submit, plan compilation, per-shard plan execution,
kernel dispatch, registry uploads, flush/compaction.  A process-global
tracer decides what those calls cost:

  NullTracer   the default: ``span()`` returns a shared no-op context
               manager, no lock, no allocation beyond the (empty) kwargs
               dict — the instrumented path stays within noise of an
               uninstrumented one (gated in ``scripts/check.sh``),
  Tracer       records (name, begin, end, thread) per span, and the
               thread's CPU time for the planner's spans (``CPU_SPANS``),
               thread-safe, bounded (drops past ``max_events``),
               exportable as Chrome trace-event JSON that loads directly
               in Perfetto / about://tracing, with one named track per
               thread — the shard worker pools are named ``shard-N``, so
               per-shard timelines come out of the box.  Each span also
               opens a ``jax.profiler.TraceAnnotation`` of the same name
               on its thread, so under ``jax.profiler.start_trace`` the
               spans land in the profile's host plane on the clock the
               device planes use.

Enable globally with env ``REPRO_TRACE=1`` (read once at import), or per
scope with ``set_tracer(Tracer())`` / the ``enabled()`` context manager.
Span names are dot-namespaced (``engine.submit``, ``shard.plan``,
``kernel.cascade``); the prefix becomes the Chrome event category.
"""

from __future__ import annotations

import json
import os
import threading
import time


class _NullSpan:
    """Shared no-op context manager (the zero-cost off switch)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracer that records nothing; every call is O(1) and lock-free."""

    enabled = False

    def span(self, name: str, **attrs) -> _NullSpan:
        return _NULL_SPAN

    def events(self) -> list:
        return []

    def clear(self) -> None:
        pass


NULL_TRACER = NullTracer()


_perf_counter = time.perf_counter
_thread_time_ns = time.thread_time_ns
_current_thread = threading.current_thread

# Span-name prefixes whose spans also take their thread's CPU time.  A
# thread-clock read is a system call, and while the shard threads run
# it costs several times what it costs alone, more than the rest of a
# recorded span; so only the planner's spans take it, whose GIL wait
# the planner is judged by.
CPU_SPANS = ("plan.",)


class _Span:
    """One open span; records on ``__exit__`` (begin/end always pair).

    Inside a profiler session the span opens a profiler annotation
    before its clocks are read and closes it after, so the annotation's
    interval holds the recorded one; outside a session it opens none."""

    __slots__ = ("tracer", "name", "attrs", "timed", "ann", "t0", "c0")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.timed = name.startswith(CPU_SPANS)

    def __enter__(self) -> "_Span":
        ann = self.tracer._annotation
        if ann.is_enabled():
            ann = ann(self.name)
            ann.__enter__()
            self.ann = ann
        else:
            self.ann = None
        if self.timed:
            self.c0 = _thread_time_ns()
        self.t0 = _perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Add attrs known only once the span's work is done."""
        self.attrs.update(attrs)

    def __exit__(self, *exc) -> bool:
        t1 = _perf_counter()
        cpu = (_thread_time_ns() - self.c0) * 1e-9 if self.timed else None
        if self.ann is not None:
            self.ann.__exit__(*exc)
        # Recorded without the tracer's lock: list.append is atomic, so
        # spans closing on several threads at once never tear, and the
        # bound may overshoot by a span per thread racing past the check.
        tr = self.tracer
        if len(tr._events) >= tr.max_events:
            tr.dropped += 1
            return False
        th = _current_thread()
        tr._events.append((self.name, self.t0, t1, cpu, th.ident, th.name,
                           self.attrs))
        return False


class Tracer:
    """Thread-safe span recorder on the monotonic ``perf_counter`` clock.

    Every span is stored as a completed ``(name, t0, t1, cpu, tid,
    thread name, attrs)`` tuple — begin/end pair by construction,
    timestamps are monotonic and shared across threads (one clock);
    ``cpu`` is the CPU time the span's thread ran inside it
    (``time.thread_time_ns``) for a span whose name starts with one of
    ``CPU_SPANS``, else None; wall minus ``cpu`` is the time the thread
    waited: on I/O, on the device, or for the GIL.  Memory is bounded:
    past ``max_events`` spans, new ones are counted in ``dropped`` and
    discarded (the trace stays loadable, never OOMs a long run).
    """

    enabled = True

    def __init__(self, max_events: int = 1_000_000):
        self.max_events = int(max_events)
        self.dropped = 0
        self._events: list[tuple] = []
        self._lock = threading.Lock()
        self._base = time.perf_counter()
        # Imported here, not at module import: the NullTracer path never
        # touches JAX.
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation

    # ----------------------------------------------------------- record
    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._base = time.perf_counter()

    # ------------------------------------------------------------ views
    def events(self) -> list[dict]:
        """Completed spans as dicts (seconds on the tracer's clock;
        ``cpu``: seconds the thread ran inside the span, or None)."""
        with self._lock:
            snap = list(self._events)
        return [{"name": n, "t0": t0, "t1": t1, "cpu": cpu, "tid": tid,
                 "thread": tname, "attrs": attrs}
                for n, t0, t1, cpu, tid, tname, attrs in snap]

    def chrome_events(self) -> list[dict]:
        """Chrome trace-event list: complete ('X') events in microseconds
        relative to the tracer epoch, plus thread/process name metadata
        so Perfetto labels each shard thread's track.  Each event's
        ``args`` holds the span's attrs, and ``cpu_ms`` where the span
        took its thread's CPU time."""
        with self._lock:
            snap = list(self._events)
            base = self._base
        out = [{"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
                "args": {"name": "repro-engine"}}]
        seen: set[int] = set()
        for name, t0, t1, cpu, tid, tname, attrs in snap:
            if tid not in seen:
                seen.add(tid)
                out.append({"name": "thread_name", "ph": "M", "pid": 1,
                            "tid": tid, "args": {"name": tname}})
            ev = {"name": name, "cat": name.split(".", 1)[0], "ph": "X",
                  "pid": 1, "tid": tid,
                  "ts": round((t0 - base) * 1e6, 3),
                  "dur": round((t1 - t0) * 1e6, 3)}
            if cpu is not None:
                attrs = {**attrs, "cpu_ms": round(cpu * 1e3, 6)}
            if attrs:
                ev["args"] = attrs
            out.append(ev)
        return out

    def export_chrome(self, path: str) -> dict:
        """Write the Chrome/Perfetto trace JSON; returns the document."""
        doc = {"traceEvents": self.chrome_events(),
               "displayTimeUnit": "ms",
               "otherData": {"clock": "perf_counter",
                             "dropped_events": self.dropped}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return doc


# ------------------------------------------------------- global dispatch
def _from_env() -> NullTracer | Tracer:
    return Tracer() if os.environ.get("REPRO_TRACE", "0") not in \
        ("0", "", "off") else NULL_TRACER


_TRACER = _from_env()


def get_tracer():
    """The process-global tracer all instrumented call sites use."""
    return _TRACER


def set_tracer(tracer) -> None:
    """Install ``tracer`` globally (``NULL_TRACER`` to disable)."""
    global _TRACER
    _TRACER = tracer if tracer is not None else NULL_TRACER


def span(name: str, **attrs):
    """Open a span on the global tracer (a no-op when tracing is off).

    Hot call sites pass at most a couple of scalar attrs; anything
    costly to compute should be guarded with ``tracing_enabled()``.
    """
    return _TRACER.span(name, **attrs)


def tracing_enabled() -> bool:
    return _TRACER.enabled


class enabled:
    """Scope with a fresh recording ``Tracer`` installed globally.

        with obs.enabled() as tr:
            engine.get_batch(keys)
        tr.export_chrome("trace.json")
    """

    def __init__(self, tracer: Tracer | None = None):
        self.tracer = tracer or Tracer()
        self._prev = None

    def __enter__(self) -> Tracer:
        self._prev = get_tracer()
        set_tracer(self.tracer)
        return self.tracer

    def __exit__(self, *exc) -> bool:
        set_tracer(self._prev)
        return False
