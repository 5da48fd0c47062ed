"""The 4-clique cell's byte counts, at the scale-16 shapes of its
configuration, and its metric readers on a run with no trace."""
import small  # noqa: F401  (puts the checkout and src on sys.path)
from pgbench import cliques_roofline as CR
from pgbench import harness

N, M, W, T = 65_536, 910_200, 116, 15_588_932


def test_scale16_bytes():
    assert CR.triple_and_min_bytes(N, W, T) == 279_831_616
    assert round(CR.triple_and_min_bytes(N, W, T) / 819e9 * 1e3, 3) == 0.342
    assert CR.triple_and_gathered_bytes(W, T) == T * 3 * W * 4   # 21.7 GB
    assert CR.triangle_list_min_bytes(N, M, T) == 198_776_024 == (
        (N + 1) * 4 + 2 * M * 4 + N * 4 + (N + 1) * 4 + M * 4 + T * 12)


def test_readers_quiet_without_a_trace_or_a_triangle_count():
    """The parent lacks the triangle list: its runs have no ``triangles``
    shape, and every reader then returns None rather than raising."""
    run = harness.Run(0.0)
    run.window_t0 = 0.0
    run.shapes = {"n": N, "m": M, "words": W}
    run.trace = {"span_count": {}, "span_busy_s": {}}
    run.peaks = {"hbm_bytes_per_s": 819e9}
    for name in ("triangle_list_s", "triangle_list_hbm_roofline_pct",
                 "triple_and_hbm_roofline_pct"):
        path = harness.BENCH_DIR / "metrics" / f"{name}.py"
        assert harness._module(path).read(run) is None, name
