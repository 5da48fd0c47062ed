"""Unified observability layer: span tracing, metrics, accuracy telemetry.

- :mod:`repro.obs.trace` — structured span tracer with Chrome-trace/Perfetto
  export, instrumented through the engine/stream/serving hot seams; while
  enabled its spans also land in ``jax.profiler`` traces, and JAX's
  compile steps are counted against the open span.
- :mod:`repro.obs.metrics` — labelled counter/gauge/histogram registry; the
  ad-hoc stat dicts (``TrafficMeter``, ``server.stats()``) are views over it.
- :mod:`repro.obs.accuracy` — sketch fill-ratio and live error-bound gauges.

Import rule: ``obs`` depends only on numpy/stdlib (plus a lazy ``jax``
import for span fencing, profiler annotations and compile-step listeners),
so every other layer may import it freely without cycles.
"""
from . import accuracy, metrics, trace
from .metrics import REGISTRY, MetricsRegistry
from .trace import span, traced

__all__ = ["REGISTRY", "MetricsRegistry", "accuracy", "metrics", "span",
           "trace", "traced"]
