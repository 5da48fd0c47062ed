"""Reduce a JAX profiler trace to the benchmark's device numbers.

Device time comes from the TPU planes' ``XLA Ops`` lines: busy time is the
union of the intervals in which an operation ran, per chip, averaged over
the chips. Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
events (names starting with ``pgbench.``) on the host plane, on the same
clock. From these the reduction gives:

- ``busy_s`` and ``window_s`` over the ``pgbench.window`` span;
- the device-busy seconds inside each span name (summed over its spans);
- ``breakdown``: the ten device operations with the most time, and the idle
  time inside the window grouped by the innermost benchmark span that was
  open in the middle of each gap, ten largest.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

import numpy as np

SPAN_PREFIX = "pgbench."
WINDOW = "pgbench.window"
OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` file a profiler session wrote under a dir."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _merge(intervals):
    """Union of [start, end) intervals, as sorted disjoint arrays."""
    if not intervals:
        return np.zeros(0), np.zeros(0)
    iv = np.asarray(sorted(intervals), dtype=np.float64)
    starts, ends = [iv[0, 0]], [iv[0, 1]]
    for s, e in iv[1:]:
        if s <= ends[-1]:
            ends[-1] = max(ends[-1], e)
        else:
            starts.append(s)
            ends.append(e)
    return np.asarray(starts), np.asarray(ends)


def _overlap(starts, ends, lo, hi) -> float:
    """Length of [lo, hi) covered by the disjoint sorted intervals."""
    if starts.size == 0 or hi <= lo:
        return 0.0
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    total = 0.0
    while i < starts.size and starts[i] < hi:
        total += max(0.0, min(ends[i], hi) - max(starts[i], lo))
        i += 1
    return total


def read_events(path: str):
    """``(device_ops, spans)`` from one xplane file.

    ``device_ops`` maps each device plane name to a list of
    ``(name, start_ns, end_ns)``; ``spans`` is a list of
    ``(name, start_ns, end_ns)`` of the benchmark's host spans.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return device_ops, spans


def reduce_events(device_ops: dict, spans: list) -> dict:
    """The benchmark's numbers from already-read trace events (see module
    docstring); all times in seconds. Returns None-valued busy numbers when
    the trace holds no device operation."""
    windows = [s for s in spans if s[0] == WINDOW]
    if windows:
        w_lo, w_hi = min(s[1] for s in windows), max(s[2] for s in windows)
    else:
        all_t = [t for ops in device_ops.values() for _, a, b in ops for t in
                 (a, b)]
        w_lo, w_hi = (min(all_t), max(all_t)) if all_t else (0.0, 0.0)
    merged = {dev: _merge([(a, b) for _, a, b in ops])
              for dev, ops in device_ops.items()}
    n_dev = max(len(merged), 1)

    def busy(lo, hi):
        return sum(_overlap(s, e, lo, hi) for s, e in merged.values()) / n_dev

    span_busy = defaultdict(float)
    span_wall = defaultdict(float)
    span_count = defaultdict(int)
    for name, a, b in spans:
        span_busy[name] += busy(a, b) * 1e-9
        span_wall[name] += (b - a) * 1e-9
        span_count[name] += 1

    op_time = defaultdict(float)
    for ops in device_ops.values():
        for name, a, b in ops:
            op_time[name] += (min(b, w_hi) - max(a, w_lo)) * 1e-9 / n_dev \
                if b > w_lo and a < w_hi else 0.0
    device_ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]

    idle = defaultdict(float)
    if merged:
        starts, ends = next(iter(merged.values()))
        lo = np.concatenate([[w_lo], np.clip(ends, w_lo, w_hi)])
        hi = np.concatenate([np.clip(starts, w_lo, w_hi), [w_hi]])
        gap = hi > lo
        lo, hi = lo[gap], hi[gap]
        mid = 0.5 * (lo + hi)
        order = np.argsort(mid)
        mid_sorted = mid[order]
        label = np.full(mid.size, -1)
        names = []
        # outermost spans first, so an inner span overwrites its parent
        for k, (name, a, b) in enumerate(
                sorted(spans, key=lambda s: s[1] - s[2])):
            names.append(name)
            i, j = np.searchsorted(mid_sorted, [a, b])
            label[order[i:j]] = k
        for k, length in zip(label, hi - lo):
            idle[names[k] if k >= 0 else "outside_spans"] += length * 1e-9
    idle_top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]

    return {
        "window_s": (w_hi - w_lo) * 1e-9,
        "busy_s": busy(w_lo, w_hi) * 1e-9 if merged else None,
        "devices": len(merged),
        "span_busy_s": dict(span_busy),
        "span_wall_s": dict(span_wall),
        "span_count": dict(span_count),
        "breakdown": {"device_ops": [[k, v] for k, v in device_ops_top],
                      "idle_gaps": [[k, v] for k, v in idle_top]},
    }


def reduce_trace(path: str) -> dict:
    """:func:`reduce_events` over one xplane file."""
    return reduce_events(*read_events(path))
