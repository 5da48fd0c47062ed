"""The benchmark harness: one cell, one run, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file, the traffic file (``pgbench/traffic/<traffic>.json``,
whose ``loop`` names its module in ``pgbench/loops/`` and whose ``check``
names its output check in ``pgbench/checks/``), the limits of that check
(``pgbench/limits/<cell>.json``) and one reader per metric
(``pgbench/metrics/<metric>.py``, a ``read(run)`` that returns a number or
None). A later cell, configuration, traffic mix or metric is new files and
new ``BENCHMARK.json`` entries; no file here changes for it.

A run: set up (inputs from the seed, the program's state, one untimed warm
step), then timed steps until ``seconds`` have passed, then the device's
peak memory, the trace reduction (``--trace 1``), the output check against
the plain reference, and the result line.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_DIR = BENCH_DIR / ".trace"


class BenchError(RuntimeError):
    """A run cannot produce a result (no chip, a pad exceeded, ...)."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(path: Path):
    """Import one benchmark file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "pgbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with its configuration, traffic, limits and
    metric names, all resolved from ``BENCHMARK.json``."""

    def __init__(self, name: str):
        bench = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = cells[name]
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(ROOT / conf["file"])
        self.traffic = load_json(
            BENCH_DIR / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = load_json(BENCH_DIR / "limits" / f"{name}.json")
        self.chips = int(self.entry["chips"])

        def applies(metric):
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m)]

    def loop(self):
        """The traffic's loop module (``pgbench/loops/<loop>.py``)."""
        return _module(BENCH_DIR / "loops" / f"{self.traffic['loop']}.py")


class Run:
    """What one run measured: host spans, window, counters, trace."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.spans = []          # (name, t0, t1) on the host clock
        self.window_s = None
        self.window_t0 = None
        self.setup_s = None
        self.trace = None        # trace_reduce output, --trace 1 only
        self.counters = {}       # filled by the loop
        self.shapes = {}         # filled by the loop
        self.peaks = None        # peaks.json row of this device

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """A benchmark span: on the profiler's clock (TraceAnnotation) and
        on the host clock (``self.spans``)."""
        import jax

        with jax.profiler.TraceAnnotation(name, **attrs):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))


def tpu_devices(chips: int):
    """The TPU devices JAX finds; a BenchError without ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts compile requests and persistent-cache hits and misses."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def use_compile_cache() -> str:
    """The program's fixed compile-cache directory inside the checkout, with
    every program written to it, so only a checkout's first run compiles."""
    import jax
    from repro.compile_cache import use_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """Run one cell once; returns the result line's dict.

    ``require_tpu=False`` exists for the CPU tests; the benchmark's own runs
    never set it.
    """
    import jax

    devices = tpu_devices(cell.chips) if require_tpu else jax.devices()[:1]
    cache_dir = use_compile_cache() if require_tpu else None
    peaks_table = load_json(BENCH_DIR / "peaks.json")["devices"]
    kind = devices[0].device_kind
    run = Run(t_start)
    if require_tpu and kind not in peaks_table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    run.peaks = peaks_table.get(kind)
    loop = cell.loop().Loop(cell.config, cell.traffic, seed, run)
    compiles = CompileCounter()
    loop.warm()
    run.setup_s = time.perf_counter() - t_start
    before = compiles.snapshot()
    if trace:
        shutil.rmtree(TRACE_DIR / cell.name, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR / cell.name))
    steps = 0
    with run.span("pgbench.window"):
        t0 = run.window_t0 = time.perf_counter()
        while steps == 0 or time.perf_counter() - t0 < seconds:
            loop.step()
            steps += 1
        run.window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = {k: compiles.snapshot()[k] - before[k] for k in before}
    compiles.close()
    stats = devices[0].memory_stats() or {}
    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devices)
    if trace:
        from . import trace_reduce

        run.trace = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(str(TRACE_DIR / cell.name)))
        shutil.rmtree(TRACE_DIR / cell.name, ignore_errors=True)
    attempted, failed = loop.counts()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = _module(BENCH_DIR / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    outputs = loop.outputs()
    del loop
    gc.collect()
    checks = cell_checks(cell, outputs, seed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {
        "correct": bool(correct and failed == 0),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if trace and run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    spans = {}
    for name, t0, t1 in run.spans:
        if t0 >= run.window_t0 and name != "pgbench.window":
            spans.setdefault(name, []).append(round(t1 - t0, 4))
    out["notes"] = {"steps": steps, "window_s": run.window_s,
                    "window_spans_s": spans,
                    "setup_s": run.setup_s, "compile_cache": cache_dir,
                    "compiles_in_window": in_window,
                    "bytes_limit": stats.get("bytes_limit")}
    out["checks"] = checks
    return out


def cell_checks(cell: Cell, outputs: dict, seed: int) -> dict:
    """The output check (``pgbench/checks/<check>.py``, named by the
    traffic): each compared number beside its limit."""
    check = _module(BENCH_DIR / "checks" / f"{cell.traffic['check']}.py")
    values = check.numbers(cell.traffic, cell.config, outputs, seed)
    missing = set(cell.limits) ^ set(values)
    if missing:
        raise BenchError(f"checks and limits disagree on {sorted(missing)}")
    return {name: {"value": values[name], "limit": cell.limits[name]}
            for name in values}
