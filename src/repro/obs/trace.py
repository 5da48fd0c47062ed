"""Low-overhead structured span tracer with Chrome-trace/Perfetto export.

One process-wide :class:`Tracer` records named spans into a thread-safe
bounded ring buffer. Spans are context managers (``with span("apply_delta",
vertices=...)``) or decorators (:func:`traced`) and nest through a
thread-local stack, so the export reconstructs the call tree without any
global locking on the hot path.

Attribution under JAX's async dispatch: a span can *fence* a device value
(``sp.fence(out)``), and span exit then calls ``jax.block_until_ready`` on
it **before** reading the clock — device work is charged to the span that
launched it instead of leaking into whichever span happens to synchronize
next. Fencing only happens while tracing is enabled; the disabled path is a
single flag check returning a shared no-op span, so instrumented code keeps
async dispatch and pays no measurable cost (the smoke-bench overhead gate
holds the line).

Export is the Chrome trace-event JSON format (``ph: "X"`` complete events,
microsecond timestamps), which loads directly in Perfetto / chrome://tracing;
``aggregate()`` gives per-span-name count/total wall time for benchmark
breakdowns.

On the profiler's clock: while enabled, every span also enters a
``jax.profiler.TraceAnnotation`` of its bare name (attributes stay in the
ring buffer), so a ``jax.profiler`` trace holds the program's spans on its
host plane. The ring buffer reads the same clock as that plane,
``CLOCK_REALTIME`` in nanoseconds (``time.time_ns()``; each event's
``start_ns``), so a span's two records agree to microseconds.

Compile steps: while enabled, ``jax.monitoring`` listeners record JAX's own
compile-step events (:data:`COMPILE_STEPS`) into the metrics registry as
``compile_step_s`` (seconds, exclusive of nested compile steps) and
``compile_step_total`` (events), both labelled ``step=`` and ``span=`` (the
innermost open program span), and into the ring buffer as ``jax.<step>``
spans whose parent is that span.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from .metrics import REGISTRY

#: JAX's compile-step monitoring events and the step name each records as.
#: The first three arrive with a start and an end on ``time.time()``; the
#: persistent-cache load arrives as a duration only, when it ends (inside
#: its ``backend_compile``).
COMPILE_STEPS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

#: the ``span=`` label of a compile step that no program span encloses
OUTSIDE = "outside_program_spans"


class _NullSpan:
    """The shared no-op span returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        """No-op attribute update (tracing disabled)."""
        return self

    def fence(self, value):
        """Pass the value through without blocking (tracing disabled)."""
        return value


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records name/attrs/parent and times its ``with`` body."""

    __slots__ = ("_tracer", "name", "attrs", "_fenced", "_t0", "_parent",
                 "_depth", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._fenced = None

    def set(self, **attrs):
        """Attach/overwrite span attributes from inside the body."""
        self.attrs.update(attrs)
        return self

    def fence(self, value):
        """Register a device value to ``block_until_ready`` at span exit, so
        its device work is attributed to this span; returns the value."""
        self._fenced = value
        return value

    def __enter__(self):
        stack = self._tracer._stack()
        self._parent = stack[-1].name if stack else None
        self._depth = len(stack)
        stack.append(self)
        self._annotation = self._tracer._annotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._fenced is not None:
            try:
                import jax
                jax.block_until_ready(self._fenced)
            except Exception:  # noqa: BLE001 - tracers/aborted buffers
                pass
            self._fenced = None
        t1 = time.time_ns()
        self._annotation.__exit__(None, None, None)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._record(self.name, self._t0, t1, self._parent,
                             self._depth, self.attrs,
                             error=exc_type is not None)
        return False


class Tracer:
    """Thread-safe bounded ring buffer of completed spans.

    Most callers use the module-level singleton through :func:`span` /
    :func:`enable` / :func:`export`; independent tracers exist mainly for
    tests.
    """

    def __init__(self, capacity: int = 65536):
        self.capacity = int(capacity)
        self.enabled = False
        self._events: "collections.deque[dict]" = collections.deque(
            maxlen=self.capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._origin = time.time_ns()
        self._annotation = None    # jax.profiler.TraceAnnotation once enabled
        self._listeners = (self._on_time_span, self._on_duration)
        self.recorded = 0          # total spans ever recorded (ring may drop)

    # -- hot path -----------------------------------------------------------

    def span(self, name: str, **attrs):
        """Open a span context manager (no-op singleton while disabled)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, name: str, t0: int, t1: int,
                parent: Optional[str], depth: int, attrs: dict,
                error: bool = False) -> None:
        event = {
            "name": name,
            "ts": (t0 - self._origin) * 1e-3,     # µs since tracer origin
            "dur": (t1 - t0) * 1e-3,
            "start_ns": t0,                       # the profiler's clock
            "tid": threading.get_ident(),
            "parent": parent,
            "depth": depth,
            "args": attrs,
        }
        if error:
            event["error"] = True
        with self._lock:
            self._events.append(event)
            self.recorded += 1

    # -- compile steps (jax.monitoring listeners, installed while enabled) ---

    def _on_time_span(self, event: str, start: float, end: float, **kw):
        step = COMPILE_STEPS.get(event)
        if step is not None and event != _CACHE_RETRIEVAL:
            self._compile_step(step, int(start * 1e9), int(end * 1e9),
                               kw.get("fun_name"))

    def _on_duration(self, event: str, duration: float, **kw):
        if event == _CACHE_RETRIEVAL:
            t1 = time.time_ns()
            self._compile_step(COMPILE_STEPS[event],
                               t1 - int(duration * 1e9), t1, None)

    def _compile_step(self, step: str, t0: int, t1: int,
                      fun_name: Optional[str]) -> None:
        """Count one compile step against the innermost open span.

        Steps nest (a jitted function traced inside another's trace, a
        cache load inside its backend compile) and are reported as they
        end, inner first; the seconds counted are the step's own, less the
        steps reported inside it, so the counters sum to host time.
        """
        stack = self._stack()
        parent = stack[-1].name if stack else None
        done = getattr(self._local, "steps_done", None)
        if done is None:
            done = self._local.steps_done = []
        inner = 0
        while done and done[-1][0] >= t0:
            a, b = done.pop()
            inner += b - a
        done.append((t0, t1))
        del done[:-256]          # top-level steps pile up; keep the recent
        label = parent or OUTSIDE
        REGISTRY.gauge("compile_step_s", step=step, span=label).add(
            (t1 - t0 - inner) * 1e-9)
        REGISTRY.counter("compile_step_total", step=step, span=label).inc()
        self._record("jax." + step, t0, t1, parent, len(stack),
                     {"fun_name": fun_name} if fun_name else {})

    # -- control ------------------------------------------------------------

    def enable(self, capacity: Optional[int] = None) -> None:
        """Turn tracing on (optionally resizing the ring buffer); spans then
        also annotate profiler traces, and the compile-step listeners are
        installed."""
        if capacity is not None and int(capacity) != self.capacity:
            self.capacity = int(capacity)
            with self._lock:
                self._events = collections.deque(self._events,
                                                 maxlen=self.capacity)
        if not self.enabled:
            import jax
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
            on_span, on_duration = self._listeners
            jax.monitoring.register_event_time_span_listener(on_span)
            jax.monitoring.register_event_duration_secs_listener(on_duration)
        self.enabled = True

    def disable(self) -> None:
        """Turn tracing off and remove the compile-step listeners (recorded
        spans are kept until ``clear``)."""
        if self.enabled:
            import jax

            on_span, on_duration = self._listeners
            # already gone if someone cleared every jax.monitoring listener
            with contextlib.suppress(AssertionError, ValueError):
                jax.monitoring.unregister_event_time_span_listener(on_span)
            with contextlib.suppress(AssertionError, ValueError):
                jax.monitoring.unregister_event_duration_listener(on_duration)
        self.enabled = False

    def clear(self) -> None:
        """Drop every recorded span and reset the time origin."""
        with self._lock:
            self._events.clear()
            self.recorded = 0
            self._origin = time.time_ns()

    # -- reads --------------------------------------------------------------

    def events(self) -> List[dict]:
        """A snapshot list of the recorded span events (oldest first)."""
        with self._lock:
            return list(self._events)

    def aggregate(self) -> Dict[str, dict]:
        """Per-span-name ``{"count", "total_s", "mean_s"}`` breakdown."""
        out: Dict[str, dict] = {}
        for ev in self.events():
            agg = out.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += ev["dur"] * 1e-6
        for agg in out.values():
            agg["mean_s"] = agg["total_s"] / agg["count"]
        return out

    def export(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON (loads in Perfetto); optionally written
        to ``path``.

        Every span becomes one complete event (``ph: "X"``) with
        microsecond ``ts``/``dur``; span attributes plus the recorded
        parent/depth land under ``args`` so tools (and tests) can rebuild
        the span tree without timestamp containment heuristics.
        """
        pid = os.getpid()
        tids: Dict[int, int] = {}
        trace_events = []
        for ev in self.events():
            tid = tids.setdefault(ev["tid"], len(tids))
            args = dict(ev["args"])
            args["parent"] = ev["parent"]
            args["depth"] = ev["depth"]
            trace_events.append({
                "name": ev["name"], "cat": "repro", "ph": "X",
                "ts": round(ev["ts"], 3), "dur": round(ev["dur"], 3),
                "pid": pid, "tid": tid, "args": args,
            })
        doc = {"traceEvents": trace_events, "displayTimeUnit": "ms",
               "otherData": {"recorded": self.recorded,
                             "capacity": self.capacity,
                             "origin_ns": self._origin}}
        if path:
            with open(path, "w") as fh:
                json.dump(doc, fh)
        return doc


#: the process-wide tracer every instrumented seam records into
TRACER = Tracer()


def span(name: str, **attrs):
    """Open a span on the global tracer: ``with trace.span("x", k=v) as sp``.

    Returns a shared no-op object while tracing is disabled — safe (and
    near-free) to leave in hot paths. Keep attribute expressions cheap at
    call sites: they are evaluated even when disabled.
    """
    if not TRACER.enabled:
        return _NULL_SPAN
    return _Span(TRACER, name, attrs)


def traced(name: Optional[str] = None, **attrs):
    """Decorator form: ``@traced("engine.refresh")`` wraps calls in a span."""
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(label, **attrs):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def enable(capacity: Optional[int] = None) -> None:
    """Enable the global tracer (see :meth:`Tracer.enable`)."""
    TRACER.enable(capacity)


def disable() -> None:
    """Disable the global tracer (recorded spans kept)."""
    TRACER.disable()


def enabled() -> bool:
    """Is the global tracer currently recording?"""
    return TRACER.enabled


def clear() -> None:
    """Drop the global tracer's recorded spans."""
    TRACER.clear()


def events() -> List[dict]:
    """Snapshot of the global tracer's span events."""
    return TRACER.events()


def aggregate() -> Dict[str, dict]:
    """Per-span-name breakdown of the global tracer's events."""
    return TRACER.aggregate()


def export(path: Optional[str] = None) -> dict:
    """Chrome-trace export of the global tracer (see :meth:`Tracer.export`)."""
    return TRACER.export(path)


__all__ = ["COMPILE_STEPS", "OUTSIDE", "TRACER", "Tracer", "aggregate",
           "clear", "disable", "enable", "enabled", "events", "export",
           "span", "traced"]
