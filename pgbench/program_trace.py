"""Run one cell with the program's own tracing on, and charge its device
time to the program spans that launched it.

    python3 pgbench/program_trace.py --workload <cell> --seed <n> --seconds <s> [--keep <dir>]

Run from the root of a checkout, on the chips the cell asks for, like
``pgbench/run.py``. After the cell's set-up it runs three windows of
``--seconds`` on the same state: ``off`` (nothing traced), ``profiler``
(the ``jax.profiler`` trace alone, as a ``--trace 1`` run has it) and
``program`` (the profiler with the program's tracer, ``repro.obs.trace``,
cleared and enabled before the trace starts and disabled after it stops).
The last line of standard output is one JSON object: ``mine_job_s`` and
``jobs`` of each window; for ``program`` the reduction of
``pgbench/launch_reduce.py`` over its trace and ring buffer, the
compile-step seconds and events counted inside the window (by step and
span), the start of each program span in the trace less its start in the
ring buffer (``clock_offset_us``), and the per-layer numbers of
:data:`READERS`. ``--keep`` copies the ``program`` window's trace and ring buffer into
a directory (with ``--seconds 0``, one job a window: a test fixture).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def program_load_s(run):
    """Host seconds of JAX compile steps (tracing, lowering, backend compile,
    persistent-cache loads) counted inside the window, per job."""
    jobs = run.counters.get("jobs")
    steps = getattr(run, "compile_steps", None)
    if run.trace is None or steps is None or not jobs:
        return None
    return sum(s for s, _ in steps.values()) / jobs


def _span_device_per_job(span):
    def read(run):
        jobs = run.counters.get("jobs")
        if run.trace is None or not jobs:
            return None
        seconds = run.trace.get("span_device_s", {}).get(span)
        return None if seconds is None else seconds / jobs
    read.__doc__ = (f"Device seconds launched inside ``{span}`` in the "
                    "window, per job.")
    return read


#: the per-layer numbers of a ``program`` window, by metric name
READERS = {
    "program_load_s": program_load_s,
    "jp_label_prop_device_s": _span_device_per_job("jp.label_propagation"),
    "sketch_build_device_s": _span_device_per_job("sketch.bloom_build"),
}


def compile_steps() -> dict:
    """``{(step, span): (seconds, events)}`` of the program's compile-step
    counters now."""
    from repro.obs.metrics import REGISTRY

    seconds = REGISTRY.labelled("compile_step_s")
    events = REGISTRY.labelled("compile_step_total")
    out = {}
    for labels, gauge in seconds.items():
        d = dict(labels)
        count = events.get(labels)
        out[d["step"], d["span"]] = (gauge.value,
                                     0 if count is None else count.value)
    return out


def _since(after: dict, before: dict) -> dict:
    out = {}
    for key, (s, n) in after.items():
        s0, n0 = before.get(key, (0.0, 0))
        if n > n0:
            out[key] = (s - s0, n - n0)
    return out


def _window(loop, run, seconds: float) -> tuple:
    """Whole jobs for ``seconds`` inside a ``pgbench.window`` span, as the
    harness runs them; returns ``(jobs, wall seconds)``."""
    jobs = 0
    with run.span("pgbench.window"):
        t0 = time.perf_counter()
        while jobs == 0 or time.perf_counter() - t0 < seconds:
            loop.step()
            jobs += 1
        return jobs, time.perf_counter() - t0


def trace_cell(cell, seed: int, seconds: float, t_start: float,
               require_tpu: bool = True, keep=None) -> dict:
    """The three windows of one cell (see the module docstring).

    ``require_tpu=False`` exists for the CPU tests.
    """
    import jax

    from pgbench import harness
    from pgbench import launch_reduce as L
    from pgbench import trace_reduce as T
    from repro.obs import trace

    devices = harness.tpu_devices(cell.chips) if require_tpu \
        else jax.devices()[:1]
    if require_tpu:
        harness.use_compile_cache()
    run = harness.Run(t_start)
    run.peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")[
        "devices"].get(devices[0].device_kind)
    loop = cell.loop().Loop(cell.config, cell.traffic, seed, run)
    loop.warm()
    out = {"workload": cell.name, "seed": seed,
           "setup_s": time.perf_counter() - t_start,
           "device": devices[0].device_kind, "windows": {}}
    trace_dir = harness.TRACE_DIR / f"{cell.name}.program"
    for name in ("off", "profiler", "program"):
        program = name == "program"
        if name != "off":
            shutil.rmtree(trace_dir, ignore_errors=True)
            if program:
                trace.clear()
                trace.enable()
            jax.profiler.start_trace(str(trace_dir))
        before = compile_steps()
        jobs, wall = _window(loop, run, seconds)
        steps = _since(compile_steps(), before)
        if name != "off":
            jax.profiler.stop_trace()
        if program:
            trace.disable()
        row = out["windows"][name] = {"jobs": jobs, "window_s": wall,
                                      "mine_job_s": wall / jobs}
        if name == "off":
            continue
        xplane = T.find_xplane(str(trace_dir))
        if not program:
            reduced = T.reduce_trace(xplane)
            row.update(busy_s=reduced["busy_s"],
                       trace_window_s=reduced["window_s"])
            continue
        ring = trace.events()
        ev = L.read_events(xplane)
        reduced = L.reduce(ev, ring)
        offsets = L.clock_offsets_us(ev, ring)
        run.trace, run.counters["jobs"] = reduced, jobs
        run.compile_steps = steps
        row.update(
            busy_s=reduced["busy_s"], trace_window_s=reduced["window_s"],
            span_device_s=reduced["span_device_s"],
            launch_linkage=reduced["launch_linkage"],
            breakdown=reduced["breakdown"],
            compile_steps={f"{step}/{span}": [s, n]
                           for (step, span), (s, n) in sorted(steps.items())},
            ring_events=len(ring), ring_recorded=trace.TRACER.recorded,
            clock_offset_us={
                "spans": len(offsets),
                "max_abs": max(map(abs, offsets)) if offsets else None,
                "median": statistics.median(offsets) if offsets else None},
            metrics={k: f(run) for k, f in READERS.items()})
        if keep:
            dest = Path(keep)
            dest.mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, dest / f"{cell.name}.xplane.pb")
            with open(dest / f"{cell.name}.ring.json", "w") as fh:
                json.dump(ring, fh)
        trace.clear()
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from pgbench import harness

    try:
        out = trace_cell(harness.Cell(args.workload), args.seed,
                         args.seconds, T_START, keep=args.keep)
    except harness.BenchError as exc:
        print(f"pgbench: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
