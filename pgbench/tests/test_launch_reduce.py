"""Device time charged to the program span that launched it
(``pgbench/launch_reduce.py``): on the committed TPU v5e traces and on
synthetic events."""
from pathlib import Path

import pytest

import small  # noqa: F401  (paths)
from pgbench import launch_reduce as L
from pgbench import trace_reduce as T

DATA = Path(__file__).parent / "data"
PROBE = DATA / "v5e_probe.xplane.pb"


def test_probe_launch_linkage():
    """Every execution in the probe has its ``DoEnqueueProgram``, found
    back on the Python thread through the execute call's flow."""
    ev = L.read_events(str(PROBE))
    runs = sorted(r for mods in ev.modules.values() for _, _, r in mods)
    assert runs == [5, 6, 7, 8, 9, 10]
    assert sorted(ev.enqueues) == runs
    launcher, linkage = L.launching_spans(ev)
    assert linkage == {"enqueued": 6, "via_flow": 6}
    # the probe has no program spans: everything is launched outside them
    assert set(launcher.values()) == {L.OUTSIDE}
    r = L.reduce_launches(ev)
    assert r["launch_linkage"]["linked"] == r["launch_linkage"]["executions"]
    assert r["span_device_s"] == {
        L.OUTSIDE: pytest.approx(T.reduce_trace(str(PROBE))["busy_s"])}


def test_probe_existing_outputs_unchanged():
    """Every key ``trace_reduce`` gives keeps its exact value."""
    before = T.reduce_trace(str(PROBE))
    after = L.reduce_trace(str(PROBE))
    for key, value in before.items():
        if key != "breakdown":
            assert after[key] == value, key
    for key, value in before["breakdown"].items():
        assert after["breakdown"][key] == value, key
    assert set(after["breakdown"]) == set(before["breakdown"]) | {
        "device_ops_by_span", "idle_by_program_span"}


def _synthetic():
    """Two executions launched from inside ``engine.jarvis_patrick`` (one
    inside its ``jp.label_propagation``) and one outside every span. The
    enqueues run on a runtime thread, linked by flows to the Python
    thread; the second is deferred to another thread and enqueued after
    ``jp.label_propagation`` has closed, two flows from its call."""
    ev = L.Events()
    ev.profile_start_ns = 1_000_000
    ev.bench_spans = [("pgbench.window", 0, 1000)]
    ev.program_spans = [("engine.jarvis_patrick", 100, 700, "py"),
                        ("jp.label_propagation", 300, 600, "py")]
    ev.modules = {"/device:TPU:0": [(150, 250, 1), (350, 550, 2),
                                    (800, 900, 3)]}
    ev.device_ops = {"/device:TPU:0": [
        ("%a", 150, 200), ("%b", 190, 250),            # run 1: 100 busy
        ("%while", 350, 550), ("%inner", 400, 450),    # run 2: nested ops
        ("%c", 800, 850),                              # run 3
        ("%stray", 950, 960)]}                         # in no module
    ev.enqueues = {1: ("rt", 145), 2: ("tasks", 650), 3: ("rt", 795)}
    ev.consumers["rt"] = [(140, 148, 11), (340, 348, 12), (790, 798, 13)]
    ev.consumers["tasks"] = [(640, 660, 22)]
    ev.producers = {11: ("py", 120), 12: ("py", 320), 13: ("py", 780),
                    22: ("rt", 342)}
    return ev


def test_span_device_s_on_synthetic_events():
    r = L.reduce_launches(_synthetic())
    assert r["launch_linkage"] == {"enqueued": 3, "via_flow": 3,
                                   "executions": 3, "linked": 3}
    s = r["span_device_s"]
    assert s["engine.jarvis_patrick"] == pytest.approx(100e-9)
    assert s["jp.label_propagation"] == pytest.approx(200e-9)  # a union
    assert s[L.OUTSIDE] == pytest.approx(60e-9)         # run 3 + the stray
    # the spans' shares sum to the window's busy time
    busy = T.reduce_events(_synthetic().device_ops,
                           _synthetic().bench_spans)["busy_s"]
    assert sum(s.values()) == pytest.approx(busy)
    ops = dict(r["breakdown"]["device_ops_by_span"])
    assert ops["jp.label_propagation/%while"] == pytest.approx(200e-9)
    assert ops["jp.label_propagation/%inner"] == pytest.approx(50e-9)


def test_launch_without_flow_is_placed_by_time():
    ev = _synthetic()
    ev.producers = {}
    launcher, linkage = L.launching_spans(ev)
    assert linkage["via_flow"] == 0
    # the deferred enqueue lands after its span closed: time alone
    # charges it to the parent
    assert launcher == {1: "engine.jarvis_patrick",
                        2: "engine.jarvis_patrick", 3: L.OUTSIDE}


def test_idle_by_program_span_with_ring_steps():
    ev = _synthetic()
    # a cache load inside JP, recorded in the ring buffer at 620-680 ns
    ring = [{"name": "jax.cache_retrieval", "start_ns": 1_000_620,
             "dur": 0.06, "parent": "engine.jarvis_patrick"},
            {"name": "engine.jarvis_patrick", "start_ns": 1_000_100,
             "dur": 0.6, "parent": None}]
    idle = dict(L.reduce_launches(ev, ring)["breakdown"]
                ["idle_by_program_span"])
    # gaps: 0-150 (mid 75: outside), 250-350 (mid 300: jp.label_prop
    # opens at 300), 550-800 (mid 675: the cache load), 850-950 and
    # 960-1000 (outside)
    assert idle == {
        L.OUTSIDE: pytest.approx((150 + 100 + 40) * 1e-9),
        "jp.label_propagation": pytest.approx(100e-9),
        "jax.cache_retrieval": pytest.approx(250e-9)}
    assert L.clock_offsets_us(ev, ring) == [pytest.approx(0.0)]


# One mine.query job (Graph500 scale 16) traced on a TPU v5e with the
# program's tracer on: ``python3 pgbench/program_trace.py --workload
# mine.query --seed 2147700102 --seconds 0 --keep <dir>``, its ``program``
# window. The ring buffer beside it holds the same job's spans and compile
# steps.
JOB = DATA / "v5e_mine_query_job.xplane.pb.gz"
JOB_RING = DATA / "v5e_mine_query_job.ring.json"
JOB_SPANS = ["engine.edge_cards", "engine.jarvis_patrick",
             "engine.local_clustering", "engine.plan_for", "engine.session",
             "engine.triangle_count", "jp.label_propagation",
             "jp.similarity"]


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    import gzip
    import json
    import shutil

    path = tmp_path_factory.mktemp("job") / "job.xplane.pb"
    with gzip.open(JOB) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return L.read_events(str(path)), json.loads(JOB_RING.read_text())


def test_job_program_spans_on_the_ring_clock(job):
    ev, ring = job
    assert sorted(name for name, *_ in ev.program_spans) == JOB_SPANS
    # one clock: each span's trace start is its ring-buffer start
    offsets = L.clock_offsets_us(ev, ring)
    assert len(offsets) == len(JOB_SPANS)
    assert max(map(abs, offsets)) < 100.0


def test_job_launches_all_followed_back(job):
    """Every execution has its enqueue, and every enqueue is followed back
    by flows to the Python thread, also those a runtime thread deferred
    until after the call (two flows: the deferred issue, then the
    execute call)."""
    ev, ring = job
    launcher, linkage = L.launching_spans(ev)
    assert linkage == {"enqueued": 63, "via_flow": 63}
    r = L.reduce(ev, ring)
    assert r["launch_linkage"]["executions"] == r["launch_linkage"]["linked"]
    s = r["span_device_s"]
    assert sum(s.values()) == pytest.approx(r["busy_s"])
    assert L.OUTSIDE not in s
    # JP's label-propagation while loop is the job's largest device program
    assert s["jp.label_propagation"] == max(s.values())
    top, _ = r["breakdown"]["device_ops_by_span"][0]
    assert top.startswith("jp.label_propagation/%while")


def test_job_idle_labelled(job):
    ev, ring = job
    r = L.reduce(ev, ring)
    idle = dict(r["breakdown"]["idle_by_program_span"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert idle.get(L.OUTSIDE, 0.0) < 0.2 * sum(idle.values())
    # the lowering of the retraced closures is the largest idle share
    assert max(idle, key=idle.get) == "jax.jaxpr_to_mlir_module"
