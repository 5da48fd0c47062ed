"""The trace reduction over a small trace recorded on a TPU v5e: two jitted
programs run three times inside ``pgbench.step`` spans within one
``pgbench.window`` span."""
from pathlib import Path

import pytest

import small  # noqa: F401  (paths)
from pgbench import trace_reduce as T

TRACE = Path(__file__).parent / "data" / "v5e_probe.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return T.read_events(str(TRACE))


def test_events_found(events):
    device_ops, spans = events
    assert list(device_ops) == ["/device:TPU:0"]
    assert len(device_ops["/device:TPU:0"]) == 12
    assert sorted(name for name, _, _ in spans) == [
        "pgbench.step"] * 3 + ["pgbench.window"]


def test_busy_idle_and_spans(events):
    r = T.reduce_events(*events)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.036150229)
    assert 0.0 < r["busy_s"] < r["window_s"]
    # the busy time inside the window lies inside the step spans
    assert r["span_busy_s"]["pgbench.step"] == pytest.approx(r["busy_s"])
    assert r["span_count"] == {"pgbench.window": 1, "pgbench.step": 3}
    assert r["span_wall_s"]["pgbench.step"] <= r["window_s"]


def test_breakdown(events):
    r = T.reduce_events(*events)
    ops = r["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10
    assert ops[0][0].startswith("%convolution_reduce_fusion")
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "pgbench.step"
    idle = sum(v for _, v in gaps)
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_overlap_and_merge():
    s, e = T._merge([(0, 2), (1, 3), (5, 6)])
    assert list(s) == [0, 5] and list(e) == [3, 6]
    assert T._overlap(s, e, 2, 5.5) == pytest.approx(1.5)
    assert T.reduce_events({}, [])["busy_s"] is None
