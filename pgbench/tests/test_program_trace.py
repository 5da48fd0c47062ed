"""The program-traced windows (``pgbench/program_trace.py``) on the CPU, and
its per-layer readers on synthetic runs."""
import pytest

import small
from pgbench import harness
from pgbench import program_trace as P


def test_readers_need_a_trace():
    run = harness.Run(0.0)
    run.counters["jobs"] = 4
    assert {k: f(run) for k, f in P.READERS.items()} == dict.fromkeys(
        P.READERS)


def test_readers_on_a_synthetic_run():
    run = harness.Run(0.0)
    run.counters["jobs"] = 4
    run.trace = {"span_device_s": {"jp.label_propagation": 0.6,
                                   "sketch.bloom_build": 60.0}}
    run.compile_steps = {("jaxpr_trace", "engine.jarvis_patrick"): (0.2, 4),
                         ("cache_retrieval", "engine.edge_cards"): (0.2, 4)}
    got = {k: f(run) for k, f in P.READERS.items()}
    assert got == {"program_load_s": pytest.approx(0.1),
                   "jp_label_prop_device_s": pytest.approx(0.15),
                   "sketch_build_device_s": pytest.approx(15.0)}
    run.trace = {"span_device_s": {}}           # the span launched nothing
    assert P.READERS["sketch_build_device_s"](run) is None


def test_trace_cell_on_the_cpu(tmp_path):
    cell = small.small_cell("mine.build_query", scale=8)
    out = P.trace_cell(cell, 11, 0.0, 0.0, require_tpu=False,
                       keep=str(tmp_path))
    assert set(out["windows"]) == {"off", "profiler", "program"}
    assert all(w["jobs"] == 1 for w in out["windows"].values())
    row = out["windows"]["program"]
    # every program span of the job reached the trace on the ring's clock
    assert row["clock_offset_us"]["spans"] >= 8
    assert row["clock_offset_us"]["max_abs"] < 100.0
    # a fresh session retraces Jarvis-Patrick's closures: compile steps
    assert any(key.endswith("/engine.jarvis_patrick")
               or key.endswith("/jp.label_propagation")
               for key in row["compile_steps"])
    assert row["metrics"]["program_load_s"] > 0
    assert (tmp_path / "mine.build_query.xplane.pb").exists()
    assert (tmp_path / "mine.build_query.ring.json").exists()
