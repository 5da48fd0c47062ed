"""Charge a trace's device time to the program span that launched it.

While its tracer is enabled, the program (``repro.obs.trace``) annotates a
``jax.profiler`` trace with its spans (``engine.*``, ``sketch.*``, ``jp.*``
and the other names of :data:`PROGRAM_PREFIXES`) on the host plane, and
records JAX's compile steps as ``jax.<step>`` spans in its ring buffer, on
the host plane's clock. This reduction adds to what
:func:`pgbench.trace_reduce.reduce_events` gives, and leaves every number
of that function as it is:

- ``span_device_s``: device-busy seconds inside the window by launching
  span. Each ``XLA Modules`` execution carries a ``run_id``; the host's
  ``DoEnqueueProgram`` event with the same ``run_id`` is its launch. The
  enqueue runs on a runtime thread, sometimes deferred to another one
  after the call returned, so the profiler's flow ids (the ``_c`` stat of
  an event around the enqueue, the ``_p`` stat of the event that caused
  it: ``PJRT_LoadedExecutable_Execute linkage`` on the Python thread,
  ``tpu::System::Execute`` on the runtime's) are followed back to the
  thread that holds program spans and the instant of its call. The
  execution, and the ops inside its interval, are charged to the innermost
  program span open there; the busy union is taken per span and averaged
  over the chips. What no program span launched is
  ``outside_program_spans``, so the values sum to ``busy_s``.
- ``launch_linkage``: the executions in the window, how many have a
  ``DoEnqueueProgram``, and how many of those were followed back by flows
  to a thread with program spans (the rest are placed by time alone).
- ``breakdown.device_ops_by_span``: the ten device ops with the most time,
  each named ``<launching span>/<op>``.
- ``breakdown.idle_by_program_span``: the window's idle gaps, labelled by
  the innermost program span or ``jax.<step>`` span open at each gap's
  midpoint (``outside_program_spans`` where none is).
"""
from __future__ import annotations

import bisect
from collections import defaultdict

import numpy as np

from pgbench import trace_reduce as T

#: first words of the program's span names (docs/OBSERVABILITY.md)
PROGRAM_PREFIXES = ("engine.", "sketch.", "jp.", "graph.", "stream.",
                    "server.", "cache.", "ppr.", "setexpr.")
#: compile steps, from the program's ring buffer only
STEP_PREFIX = "jax."
OUTSIDE = "outside_program_spans"
MODULE_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
#: flows followed back from an enqueue, at most (two in a TPU v5e trace)
MAX_HOPS = 8


class Events:
    """What one xplane file holds for the reductions (times in ns)."""

    def __init__(self):
        self.device_ops = {}     # device plane -> [(name, start, end)]
        self.bench_spans = []    # [(name, start, end)], names "pgbench.*"
        self.program_spans = []  # [(name, start, end, line)]
        self.modules = {}        # device plane -> [(start, end, run_id)]
        self.enqueues = {}       # run_id -> (line, t)
        self.consumers = defaultdict(list)   # line -> [(start, end, flow)]
        self.producers = {}      # flow id -> (line, t) of its cause
        self.profile_start_ns = None     # the host clock at the trace's 0


def read_events(path: str) -> Events:
    """Everything both reductions read, in one pass over an xplane file."""
    from jax.profiler import ProfileData

    ev = Events()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == T.OP_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == MODULE_LINE:
                    for e in line.events:
                        run_id = dict(e.stats).get("run_id")
                        mods.append((e.start_ns, e.start_ns + e.duration_ns,
                                     None if run_id is None else int(run_id)))
            if ops:
                ev.device_ops[plane.name] = ops
            if mods:
                ev.modules[plane.name] = sorted(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                _read_host_line(ev, line)
        elif plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
            ev.profile_start_ns = None if start is None else int(start)
    for events in ev.consumers.values():
        events.sort()
    return ev


def _read_host_line(ev: Events, line) -> None:
    for e in line.events:
        name = e.name
        if name.startswith(T.SPAN_PREFIX):
            ev.bench_spans.append((name, e.start_ns,
                                   e.start_ns + e.duration_ns))
            continue
        if name.startswith(PROGRAM_PREFIXES):
            ev.program_spans.append((name, e.start_ns,
                                     e.start_ns + e.duration_ns, line.name))
            continue
        stats = dict(e.stats)
        if "_c" in stats:
            ev.consumers[line.name].append(
                (e.start_ns, e.start_ns + e.duration_ns, int(stats["_c"])))
        if "_p" in stats:
            ev.producers[int(stats["_p"])] = (line.name, e.start_ns)
        if name == ENQUEUE and "run_id" in stats:
            ev.enqueues[int(stats["run_id"])] = (line.name, e.start_ns)


def ring_steps(ring_events, profile_start_ns) -> list:
    """The ring buffer's ``jax.<step>`` spans as ``(name, start, end)`` in
    the trace's time (ns since the profile's start)."""
    if not ring_events or profile_start_ns is None:
        return []
    out = []
    for e in ring_events:
        if e["name"].startswith(STEP_PREFIX):
            start = e["start_ns"] - profile_start_ns
            out.append((e["name"], start, start + e["dur"] * 1e3))
    return out


def _innermost(spans, times) -> list:
    """For each time, the index into ``spans`` ``[(name, start, end, ...)]``
    of the innermost span open at it, or -1."""
    times = np.asarray(times, dtype=np.float64)
    order = np.argsort(times, kind="stable")
    ordered = times[order]
    label = np.full(times.size, -1)
    # outermost spans first, so an inner span overwrites its parent
    for k in sorted(range(len(spans)), key=lambda k: spans[k][1] - spans[k][2]):
        i, j = np.searchsorted(ordered, [spans[k][1], spans[k][2]])
        label[order[i:j]] = k
    return label.tolist()


def _cause(ev: Events, line: str, t: float):
    """The ``(line, t)`` of the event that caused the innermost flow-linked
    event open at ``t`` on ``line``, or None."""
    events = ev.consumers.get(line, [])
    k = bisect.bisect_right(events, (t, float("inf"), 0)) - 1
    # latest start first, so the innermost; events nest a few deep
    for start, end, flow in reversed(events[max(k - 15, 0):k + 1]):
        if end >= t and flow in ev.producers:
            return ev.producers[flow]
    return None


def launching_spans(ev: Events) -> tuple:
    """``({run_id: span name}, linkage counts)`` over every enqueued run."""
    by_line = defaultdict(list)
    for span in ev.program_spans:
        by_line[span[3]].append(span)
    launches = defaultdict(list)    # line -> [(run_id, t)]
    via_flow = 0
    for run_id, (line, t) in ev.enqueues.items():
        hops = 0
        while line not in by_line and hops < MAX_HOPS:
            cause = _cause(ev, line, t)
            if cause is None:
                break
            line, t = cause
            hops += 1
        # followed back to a thread with spans (or, in a trace without
        # any, to the first cause)
        via_flow += hops > 0 and (line in by_line or not by_line)
        launches[line].append((run_id, t))
    names = {}
    for line, runs in launches.items():
        # a launch not followed back to a thread with spans is placed by
        # time among the spans of every thread
        spans = by_line.get(line) or ev.program_spans
        labels = _innermost(spans, [t for _, t in runs])
        for (run_id, _), k in zip(runs, labels):
            names[run_id] = spans[k][0] if k >= 0 else OUTSIDE
    return names, {"enqueued": len(ev.enqueues), "via_flow": via_flow}


def reduce_launches(ev: Events, ring_events=None) -> dict:
    """``span_device_s``, ``launch_linkage`` and the two breakdowns (see
    the module docstring); all times in seconds."""
    windows = [s for s in ev.bench_spans if s[0] == T.WINDOW]
    if windows:
        w_lo, w_hi = min(s[1] for s in windows), max(s[2] for s in windows)
    else:
        all_t = [t for ops in ev.device_ops.values() for _, a, b in ops
                 for t in (a, b)]
        w_lo, w_hi = (min(all_t), max(all_t)) if all_t else (0.0, 0.0)
    n_dev = max(len(ev.device_ops), 1)
    launcher, linkage = launching_spans(ev)

    per_span = defaultdict(list)    # (span, device) -> op intervals
    op_time = defaultdict(float)
    executions = linked = 0
    for dev, ops in ev.device_ops.items():
        mods = ev.modules.get(dev, [])
        starts = [m[0] for m in mods]
        for m_lo, m_hi, run_id in mods:
            if m_hi > w_lo and m_lo < w_hi:
                executions += 1
                linked += run_id in ev.enqueues
        for name, a, b in ops:
            if b <= w_lo or a >= w_hi:
                continue
            k = bisect.bisect_right(starts, a) - 1
            span = OUTSIDE
            if k >= 0 and a < mods[k][1]:
                span = launcher.get(mods[k][2], OUTSIDE)
            lo, hi = max(a, w_lo), min(b, w_hi)
            per_span[span, dev].append((lo, hi))
            op_time[f"{span}/{name}"] += (hi - lo) * 1e-9 / n_dev

    span_device = defaultdict(float)
    for (span, _), intervals in per_span.items():
        s, e = T._merge(intervals)
        span_device[span] += float(np.sum(e - s)) * 1e-9 / n_dev

    idle = defaultdict(float)
    if ev.device_ops:
        s, e = T._merge([(a, b) for _, a, b in
                         next(iter(ev.device_ops.values()))])
        lo = np.concatenate([[w_lo], np.clip(e, w_lo, w_hi)])
        hi = np.concatenate([np.clip(s, w_lo, w_hi), [w_hi]])
        gap = hi > lo
        lo, hi = lo[gap], hi[gap]
        spans = ev.program_spans + ring_steps(ring_events,
                                              ev.profile_start_ns)
        for k, length in zip(_innermost(spans, 0.5 * (lo + hi)), hi - lo):
            idle[spans[k][0] if k >= 0 else OUTSIDE] += float(length) * 1e-9

    def top(table, n=None):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:n]]

    return {
        "span_device_s": dict(span_device),
        "launch_linkage": dict(linkage, executions=executions, linked=linked),
        "breakdown": {"device_ops_by_span": top(op_time, 10),
                      "idle_by_program_span": top(idle)},
    }


def clock_offsets_us(ev: Events, ring_events) -> list:
    """For each program span the ring buffer and the trace both hold, the
    trace's start less the ring buffer's, in microseconds (spans of one
    name paired in order of start)."""
    if ev.profile_start_ns is None:
        return []
    traced = defaultdict(list)
    for name, a, _, _ in ev.program_spans:
        traced[name].append(a)
    ring = defaultdict(list)
    for e in ring_events or []:
        if e["name"] in traced:
            ring[e["name"]].append(e["start_ns"] - ev.profile_start_ns)
    out = []
    for name, starts in ring.items():
        if len(starts) == len(traced[name]):
            out.extend((a - b) * 1e-3 for a, b in
                       zip(sorted(traced[name]), sorted(starts)))
    return out


def reduce(ev: Events, ring_events=None) -> dict:
    """:func:`pgbench.trace_reduce.reduce_events` over the events, with
    :func:`reduce_launches`'s keys added (its breakdowns inside
    ``breakdown``)."""
    out = T.reduce_events(ev.device_ops, ev.bench_spans)
    extra = reduce_launches(ev, ring_events)
    out["breakdown"].update(extra.pop("breakdown"))
    out.update(extra)
    return out


def reduce_trace(path: str, ring_events=None) -> dict:
    """:func:`reduce` over one xplane file."""
    return reduce(read_events(path), ring_events)
