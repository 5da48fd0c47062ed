"""Observability layer: tracer, metrics registry, accuracy telemetry,
stat-facade equivalence, and the bench record schema."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import sketches as SK
from repro.obs import accuracy, trace
from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.stream.dynamic_graph import TrafficMeter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def tracing():
    """Enable the global tracer for one test, restoring the disabled state."""
    trace.enable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_records_parent_and_depth(tracing):
    with trace.span("outer", a=1):
        with trace.span("inner") as sp:
            sp.set(b=2)
    evs = trace.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert inner["parent"] == "outer" and inner["depth"] == 1
    assert outer["parent"] is None and outer["depth"] == 0
    assert outer["args"] == {"a": 1}
    assert inner["args"] == {"b": 2}
    assert inner["dur"] <= outer["dur"]
    assert inner["ts"] >= outer["ts"]


def test_disabled_tracer_returns_shared_null_span():
    assert not trace.enabled()
    s1 = trace.span("x", huge=1)
    s2 = trace.span("y")
    assert s1 is s2                      # one shared no-op object
    with s1 as sp:
        assert sp.fence(42) == 42        # passthrough, no blocking
        sp.set(k=1)
    assert trace.events() == []


def test_ring_buffer_drops_oldest():
    t = trace.Tracer(capacity=4)
    t.enable()
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    t.disable()
    evs = t.events()
    assert len(evs) == 4
    assert [e["name"] for e in evs] == ["s6", "s7", "s8", "s9"]
    assert t.recorded == 10


def test_span_fence_blocks_device_value(tracing):
    with trace.span("jit") as sp:
        out = sp.fence(jnp.arange(8) * 2)
    assert out.sum() == 56
    # the eager ops' first compiles record jax.<step> spans inside it
    spans = [e for e in trace.events() if not e["name"].startswith("jax.")]
    assert spans[0]["name"] == "jit"


def test_span_records_error_flag(tracing):
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    ev = trace.events()[0]
    assert ev["name"] == "boom" and ev["error"] is True


def test_traced_decorator(tracing):
    @trace.traced("deco.fn", tag=3)
    def f(x):
        return x + 1

    assert f(1) == 2
    ev = trace.events()[0]
    assert ev["name"] == "deco.fn" and ev["args"] == {"tag": 3}


def test_export_chrome_trace_schema(tmp_path, tracing):
    with trace.span("parent", n=5):
        with trace.span("child"):
            pass
    path = tmp_path / "t.json"
    doc = trace.export(str(path))
    ondisk = json.loads(path.read_text())
    assert ondisk == doc
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["recorded"] == 2
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X" and ev["cat"] == "repro"
        assert set(ev) >= {"name", "ts", "dur", "pid", "tid", "args"}
        assert "depth" in ev["args"] and "parent" in ev["args"]
    child = next(e for e in doc["traceEvents"] if e["name"] == "child")
    assert child["args"]["parent"] == "parent"


def test_aggregate_counts_and_totals(tracing):
    for _ in range(3):
        with trace.span("a"):
            pass
    with trace.span("b"):
        pass
    agg = trace.aggregate()
    assert agg["a"]["count"] == 3 and agg["b"]["count"] == 1
    assert agg["a"]["total_s"] >= 0
    assert agg["a"]["mean_s"] == pytest.approx(agg["a"]["total_s"] / 3)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_instrument_identity_and_values():
    reg = MetricsRegistry()
    c = reg.counter("hits", kind="bf")
    assert reg.counter("hits", kind="bf") is c       # same (name, labels)
    assert reg.counter("hits", kind="kh") is not c
    c.inc()
    c.inc(4)
    assert reg.value("hits", kind="bf") == 5
    g = reg.gauge("fill")
    g.set(0.25)
    g.add(0.5)
    assert reg.value("fill") == 0.75
    assert reg.value("never_created") is None


def test_registry_snapshot_flat_names_and_histograms():
    reg = MetricsRegistry()
    reg.counter("served", kind="tc").inc(2)
    reg.gauge("fill").set(0.5)
    h = reg.histogram("lat", window=8)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["served{kind=tc}"] == 2
    assert snap["fill"] == 0.5
    assert snap["lat_count"] == 4
    assert snap["lat_mean"] == pytest.approx(2.5)
    assert snap["lat_p95"] == pytest.approx(np.percentile([1, 2, 3, 4], 95))
    assert snap["lat_max"] == 4.0
    assert json.loads(json.dumps(snap)) == snap      # JSON-serializable


def test_histogram_window_and_labelled_enumeration():
    reg = MetricsRegistry()
    h = reg.histogram("lat", window=3)
    for v in range(10):
        h.observe(float(v))
    assert h.count == 10
    np.testing.assert_array_equal(h.values(), [7.0, 8.0, 9.0])
    reg.counter("served").inc()
    reg.counter("served", kind="tc").inc(3)
    by = reg.labelled("served")
    assert {dict(k).get("kind") for k in by} == {None, "tc"}
    reg.reset()
    assert reg.snapshot() == {}


def test_concurrent_counter_increments_lose_nothing():
    # `self._value += 1` is several bytecodes; without the per-instrument
    # lock, contending threads interleave mid-RMW and increments vanish
    # (this test fails on the unlocked implementation)
    import threading
    reg = MetricsRegistry()
    per_thread, n_threads = 20000, 8

    def hammer():
        # fetch through the registry each time: exercises _get's lock too
        c = reg.counter("served", kind="race")
        g = reg.gauge("level")
        for _ in range(per_thread):
            c.inc()
            g.add(1.0)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.value("served", kind="race") == per_thread * n_threads
    assert reg.value("level") == pytest.approx(per_thread * n_threads)


def test_concurrent_histogram_observe_and_values():
    # deque iteration while another thread appends past maxlen raises
    # RuntimeError unless observe/values share the instrument lock; count
    # is an unlocked += without the fix and drops updates
    import threading
    reg = MetricsRegistry()
    h = reg.histogram("lat", window=64)
    per_thread, n_threads = 5000, 4
    errors = []

    def observe():
        try:
            for i in range(per_thread):
                h.observe(float(i))
        except RuntimeError as exc:     # pragma: no cover - the regression
            errors.append(exc)

    def read():
        try:
            for _ in range(2000):
                vals = h.values()
                assert vals.size <= 64
        except RuntimeError as exc:     # pragma: no cover - the regression
            errors.append(exc)

    threads = ([threading.Thread(target=observe) for _ in range(n_threads)]
               + [threading.Thread(target=read)])
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert h.count == per_thread * n_threads


# ---------------------------------------------------------------------------
# accuracy telemetry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["bf", "kh", "1h", "kmv"])
def test_fill_ratio_in_unit_interval(kind):
    g = G.kronecker(7, 8, seed=0)
    sk = SK.build(g, kind, storage_budget=0.5, num_hashes=2, seed=0)
    r = accuracy.fill_ratio(sk)
    assert 0.0 < r <= 1.0
    reg = MetricsRegistry()
    assert accuracy.record_fill(sk, reg) == r
    assert reg.value("sketch_fill_ratio", kind=kind) == r


def test_bf_fill_ratio_of_strided_rows():
    """Rows with padded strides (as a TPU transfer can give) still count."""
    words = np.zeros((4, 8), np.uint32)
    words[:, 0] = 0xFF                                # 8 of 192 bits per row
    sk = SK.SketchSet(data=words[:, :6], kind="bf", num_hashes=2, k=0,
                      seed=0, n=4)
    assert accuracy.fill_ratio(sk) == 8 / 192


@pytest.mark.parametrize("kind", ["bf", "kh"])
def test_record_pair_error_gauges(kind):
    g = G.kronecker(7, 8, seed=0)
    sk = SK.build(g, kind, storage_budget=0.5, num_hashes=2, seed=0)
    deg = np.asarray(g.deg)
    e = np.asarray(g.edges)[:32]
    du, dv = deg[e[:, 0]], deg[e[:, 1]]
    cards = np.minimum(du, dv).astype(np.float64)
    reg = MetricsRegistry()
    out = accuracy.record_pair_error(sk, cards, du, dv, reg)
    assert out["rmse"] > 0.0 and out["rel"] > 0.0
    assert reg.value("accuracy_err_rmse", kind=kind) == out["rmse"]
    assert reg.value("accuracy_err_rel", kind=kind) == out["rel"]
    # empty batch records nothing and returns zeros
    assert accuracy.record_pair_error(sk, [], [], [], MetricsRegistry()) == \
        {"rmse": 0.0, "rel": 0.0}


def test_record_maintenance_mirrors_stats():
    reg = MetricsRegistry()
    stats = {"kind": "bf", "rows_dirty": 3, "stale_total": 1.5,
             "rows_rebuilt": 7, "rows_incremental": 20, "deltas_applied": 4}
    accuracy.record_maintenance(stats, reg)
    assert reg.value("sketch_rows_dirty", kind="bf") == 3.0
    assert reg.value("sketch_stale_total", kind="bf") == 1.5
    assert reg.value("sketch_rows_rebuilt", kind="bf") == 7
    assert reg.value("sketch_rows_incremental", kind="bf") == 20
    assert reg.value("sketch_deltas_applied", kind="bf") == 4
    # set-not-inc: re-recording the same stats must not double
    accuracy.record_maintenance(stats, reg)
    assert reg.value("sketch_rows_rebuilt", kind="bf") == 7


# ---------------------------------------------------------------------------
# stat facades as registry views
# ---------------------------------------------------------------------------

def test_traffic_meter_is_a_registry_view():
    tm = TrafficMeter()
    tm.put(np.zeros(100, np.int32), init=True)       # 400 bytes init
    tm.begin_delta()
    tm.put(np.zeros(10, np.int32))                   # 40 bytes delta
    tm.put(np.zeros(5, np.int32))                    # +20
    tm.count_donation()
    tm.commit_step()
    assert tm.bytes_init == 400
    assert tm.bytes_delta == 60
    assert tm.bytes_total == 60
    assert tm.steps == 1
    assert tm.donated == 1
    assert tm.stats() == {"bytes_init": 400, "bytes_total": 60,
                          "bytes_last_delta": 60, "bytes_per_delta_mean": 60.0,
                          "steps": 1, "donated_updates": 1}
    # the same numbers, straight from the backing registry
    assert tm.registry.value("traffic_bytes", path="init") == 400
    assert tm.registry.value("traffic_bytes", path="delta") == 60
    assert tm.registry.value("traffic_bytes_last_delta") == 60
    assert tm.registry.value("traffic_steps") == 1
    assert tm.registry.value("device_updates_donated") == 1
    tm.begin_delta()
    assert tm.bytes_delta == 0 and tm.bytes_total == 60
    # meters do not share registries (concurrent sessions stay isolated)
    assert TrafficMeter().bytes_init == 0


def test_setexpr_compile_cache_counters():
    from repro.engine import setexpr

    setexpr.cache_clear()
    hits0 = REGISTRY.counter("setexpr_compile_total", result="hit").value
    miss0 = REGISTRY.counter("setexpr_compile_total", result="miss").value
    u, v, w = setexpr.Row(0), setexpr.Row(1), setexpr.Row(2)
    setexpr.compile_expr((u & v) - w)
    setexpr.compile_expr((u & v) - w)
    setexpr.compile_expr((u & v) - w)
    assert REGISTRY.counter("setexpr_compile_total",
                            result="miss").value == miss0 + 1
    assert REGISTRY.counter("setexpr_compile_total",
                            result="hit").value == hits0 + 2


# ---------------------------------------------------------------------------
# spans on the profiler's clock, compile-step counters
# ---------------------------------------------------------------------------

def _profile(path, body):
    """Run ``body()`` inside a ``jax.profiler`` trace written under
    ``path``; returns ``(host events [(line, name, abs start ns)],
    profile start ns)``."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    xplane, = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(xplane)
    env = next(pl for pl in data.planes if pl.name == "Task Environment")
    origin = int(dict(env.stats)["profile_start_time"])
    host = [(line.name, ev.name, origin + int(ev.start_ns))
            for pl in data.planes if pl.name.startswith("/host:")
            for line in pl.lines for ev in line.events]
    return host, origin


def test_enabled_span_lands_on_profiler_host_plane(tmp_path, tracing):
    def body():
        with trace.span("engine.profiled_probe", rows=3):
            jnp.arange(16).sum().block_until_ready()

    host, _ = _profile(tmp_path, body)
    found = [t for _, name, t in host if name == "engine.profiled_probe"]
    assert len(found) == 1               # bare name: attrs stay in the ring
    ring, = [e for e in trace.events() if e["name"] == "engine.profiled_probe"]
    assert ring["args"] == {"rows": 3}
    # the ring buffer reads the profiler host plane's clock
    assert abs(ring["start_ns"] - found[0]) < 100_000


def test_disabled_tracer_makes_no_annotation_and_no_listener(tmp_path):
    from jax._src import monitoring

    assert not trace.enabled()

    def listeners():
        return (monitoring.get_event_time_span_listeners()
                + monitoring.get_event_duration_listeners())

    on_span, on_duration = trace.TRACER._listeners
    assert on_span not in listeners() and on_duration not in listeners()

    def body():
        with trace.span("engine.disabled_probe"):
            jnp.arange(8).sum().block_until_ready()

    host, _ = _profile(tmp_path, body)
    assert "engine.disabled_probe" not in {name for _, name, _ in host}
    assert trace.events() == []
    trace.enable()
    try:
        assert on_span in listeners() and on_duration in listeners()
    finally:
        trace.disable()
    assert on_span not in listeners() and on_duration not in listeners()


def test_fresh_jit_under_span_counts_compile_steps(tracing):
    def labels(step):
        return {"step": step, "span": "engine.compile_probe"}

    before = {step: REGISTRY.counter("compile_step_total", **labels(step)
                                     ).value
              for step in ("jaxpr_trace", "backend_compile")}
    seconds0 = REGISTRY.gauge("compile_step_s",
                              **labels("backend_compile")).value
    with trace.span("engine.compile_probe"):
        def fresh(x):
            return jnp.cos(x) * 3.0 + 1.0

        jax.jit(fresh)(jnp.ones(7)).block_until_ready()
    for step, count in before.items():
        assert REGISTRY.counter("compile_step_total",
                                **labels(step)).value > count
    assert REGISTRY.gauge("compile_step_s",
                          **labels("backend_compile")).value > seconds0
    evs = trace.events()
    steps = [e for e in evs if e["name"].startswith("jax.")]
    assert {e["name"] for e in steps} >= {"jax.jaxpr_trace",
                                          "jax.jaxpr_to_mlir_module",
                                          "jax.backend_compile"}
    assert all(e["parent"] == "engine.compile_probe" and e["depth"] == 1
               for e in steps)
    assert "fresh" in {e["args"].get("fun_name") for e in steps}
    probe, = [e for e in evs if e["name"] == "engine.compile_probe"]
    for e in steps:                      # one clock: steps lie in the span
        assert probe["start_ns"] <= e["start_ns"]
        assert e["start_ns"] + e["dur"] * 1e3 <= \
            probe["start_ns"] + probe["dur"] * 1e3 + 1e3


def test_compile_step_seconds_exclude_nested_steps():
    """Steps are reported inner first; each counts its own seconds, so the
    counters sum to the host time spent in compile steps."""
    import time as _time

    def seconds(span, step):
        return REGISTRY.gauge("compile_step_s", step=step, span=span).value

    outside0 = seconds("outside_program_spans", "backend_compile")
    t = trace.Tracer()
    span_event = jax.monitoring.record_event_time_span
    t.enable()
    try:
        with t.span("engine.nested_probe"):
            base = _time.time() - 10.0
            span_event("/jax/core/compile/jaxpr_trace_duration", base + 1.0,
                       base + 1.5, fun_name="inner")
            span_event("/jax/core/compile/jaxpr_trace_duration", base + 0.5,
                       base + 2.0, fun_name="outer")
            # a cache load ends now, inside the backend compile around it
            now = _time.time()
            jax.monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
            span_event("/jax/core/compile/backend_compile_duration",
                       now - 0.5, _time.time(), fun_name="outer")
        later = _time.time() + 1.0
        span_event("/jax/core/compile/backend_compile_duration", later,
                   later + 0.125, fun_name="late")
    finally:
        t.disable()
    # 1.0 of the outer trace's own + 0.5 of the inner
    assert seconds("engine.nested_probe", "jaxpr_trace") == pytest.approx(
        1.5, abs=1e-5)
    assert REGISTRY.counter("compile_step_total", step="jaxpr_trace",
                            span="engine.nested_probe").value == 2
    assert seconds("engine.nested_probe", "cache_retrieval") == \
        pytest.approx(0.25, abs=1e-5)
    assert seconds("engine.nested_probe", "backend_compile") == \
        pytest.approx(0.25, abs=1e-2)
    outside = seconds("outside_program_spans", "backend_compile")
    assert outside - outside0 == pytest.approx(0.125, abs=1e-5)
    names = [(e["name"], e["parent"]) for e in t.events()]
    assert names == [("jax.jaxpr_trace", "engine.nested_probe"),
                     ("jax.jaxpr_trace", "engine.nested_probe"),
                     ("jax.cache_retrieval", "engine.nested_probe"),
                     ("jax.backend_compile", "engine.nested_probe"),
                     ("engine.nested_probe", None),
                     ("jax.backend_compile", None)]
    # listeners are gone once disabled: nothing more is counted
    loads0 = seconds("outside_program_spans", "cache_retrieval")
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 1.0)
    assert seconds("outside_program_spans", "cache_retrieval") == loads0
    assert len(t.events()) == len(names)


def test_mining_session_spans(tracing):
    from repro import engine as eng

    g = G.kronecker(7, 8, seed=2)
    sess = eng.session(g, "bf", storage_budget=1.0)
    jax.block_until_ready((sess.edge_cardinalities(), sess.triangle_count(),
                           sess.local_clustering(),
                           sess.jarvis_patrick("jaccard", 0.05)))
    # setexpr.compile appears only on a compile-cache miss: not asserted
    parents = {e["name"]: e["parent"] for e in trace.events()
               if not e["name"].startswith(("jax.", "setexpr."))}
    assert parents == {
        "engine.session": None,
        "sketch.bloom_build": "engine.session",
        "engine.plan_for": "engine.session",
        "engine.edge_cards": None,
        "engine.triangle_count": None,
        "engine.local_clustering": None,
        "engine.jarvis_patrick": None,
        "jp.similarity": "engine.jarvis_patrick",
        "jp.label_propagation": "engine.jarvis_patrick",
    }


def test_jarvis_patrick_compiles_once_across_sessions(tracing):
    """JP's two programs are module-level jits: a second session on the same
    graph and sketch runs them with no compile step under the ``jp.*``
    spans, and the loop's iteration count lands on its span."""
    from repro import engine as eng

    g = G.kronecker(7, 8, seed=2)
    sketch = eng.session(g, "bf", storage_budget=1.0).sketch
    jp_spans = ("jp.similarity", "jp.label_propagation")

    def jp_compiles():
        return sum(c.value for labels, c in
                   REGISTRY.labelled("compile_step_total").items()
                   if dict(labels)["span"] in jp_spans)

    first = eng.session(g, sketch).jarvis_patrick("jaccard", 0.05)
    jax.block_until_ready(first)
    before, n_events = jp_compiles(), len(trace.events())
    second = eng.session(g, sketch).jarvis_patrick("jaccard", 0.05)
    jax.block_until_ready(second)
    assert jp_compiles() == before
    new = trace.events()[n_events:]
    assert [e for e in new
            if e["name"].startswith("jax.") and e["parent"] in jp_spans] == []
    (lp,) = [e for e in new if e["name"] == "jp.label_propagation"]
    assert isinstance(lp["args"]["iterations"], int)
    assert lp["args"]["iterations"] >= 1
    np.testing.assert_array_equal(np.asarray(first[0]),
                                  np.asarray(second[0]))
    assert int(first[1]) == int(second[1])


# ---------------------------------------------------------------------------
# bench record schema (benchmarks.common)
# ---------------------------------------------------------------------------

def test_bench_emit_schema_and_derived_parsing(capsys):
    from benchmarks import common

    common.reset_records()
    common.emit("bench_x", 1500.0,
                "speedup=2.50x;rows=128;label=abc;flag")
    common.emit("bench_y", 10.0)
    assert [r["name"] for r in common.RECORDS] == ["bench_x", "bench_y"]
    rec = common.RECORDS[0]
    assert set(rec) == {"name", "wall_s", "metrics"}
    assert rec["wall_s"] == pytest.approx(1.5e-3)
    assert rec["metrics"] == {"speedup": 2.5, "rows": 128.0,
                              "label": "abc", "flag": True}
    assert common.RECORDS[1]["metrics"] == {}
    assert json.loads(json.dumps(common.RECORDS)) == common.RECORDS
    common.reset_records()
    assert common.RECORDS == [] and common.ROWS == []
    out = capsys.readouterr().out
    assert "bench_x,1500.0,speedup=2.50x;rows=128;label=abc;flag" in out


def test_dress_rehearsal_marks_warmup_span(tracing):
    from benchmarks import common

    out = common.dress_rehearsal(lambda: jnp.arange(4).sum())
    assert int(out) == 6
    names = [e["name"] for e in trace.events()]
    assert "bench.warmup" in names
