"""The 4-clique cell's output check: its bfloat16 control comes out not
correct against the cell's limits, and with the timed path broken
underneath a run's check comes out false, once for each fault (the sketch
left as the build started it, half of the triangle list summed, one column
of the list dropped, one popcount altered inside the compiled pass, the
count altered where it is returned)."""
import jax
import pytest

import small

FAULTS = ["state_unchanged", "half_list_summed", "column_dropped",
          "popcount_altered", "count_altered"]


def cell():
    return small.small_cell("mine.cliques4", 10)


def test_control_is_not_correct_by_cc4_alone():
    """bfloat16 estimates summed in float32 fail ``cc4_rel_gap``; the
    control's list, rows and popcounts are the reference's own."""
    from pgbench.checks import cliques4 as C

    c = cell()
    nums = C.control_numbers(c.config, c.traffic, 2**31 + 31)
    assert {k for k, v in c.limits.items() if nums[k] > v} \
        == {"cc4_rel_gap"}, nums


@pytest.mark.parametrize("fault", FAULTS)
def test_cliques4_fault_is_caught(monkeypatch, fault):
    import jax.numpy as jnp

    from repro import engine as eng
    from repro.core import sketches as SK
    from repro.core.algorithms import cliques as CL
    from repro.engine import engine as E

    if fault == "state_unchanged":
        monkeypatch.setattr(SK, "build_bloom",
                            lambda g, words, *a, **k: jnp.zeros(
                                (g.n, words), jnp.uint32))
    elif fault == "half_list_summed":
        real = CL._bloom_triple_sums
        monkeypatch.setattr(
            CL, "_bloom_triple_sums",
            lambda sketch, tris, count, **k: real(sketch, tris, count // 2,
                                                  **k))
    elif fault == "column_dropped":
        real = E.MiningSession.triangles

        def dropped(self):
            tris, count = real(self)
            return tris.at[:, 2].set(0), count
        monkeypatch.setattr(E.MiningSession, "triangles", dropped)
    elif fault == "popcount_altered":
        # one triangle's popcount off by one: cc4 moves by about a
        # millionth, so only the popcount total can show it
        real = eng.triple_cardinality_ones
        monkeypatch.setattr(eng, "triple_cardinality_ones",
                            lambda s, t, plan: real(s, t, plan).at[0].add(1))
        # a fresh function, so the pass is traced anew with the fault
        inner = CL._bloom_triple_sums.__wrapped__
        monkeypatch.setattr(CL, "_bloom_triple_sums", jax.jit(
            lambda *a, **k: inner(*a, **k), static_argnames=("plan", "chunk")))
    else:
        real = E.MiningSession.four_clique_count

        def altered(self, **kw):
            cc4, ones = real(self, **kw)
            return cc4 * 1.001, ones
        monkeypatch.setattr(E.MiningSession, "four_clique_count", altered)
    out = small.run_small(cell(), seed=2**31 + 29, seconds=0.5)
    assert out["correct"] is False, out["checks"]
    if fault == "popcount_altered":
        assert out["checks"]["and_ones_gap"]["value"] > 0
