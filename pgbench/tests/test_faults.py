"""With the timed path broken underneath, a run's check comes out false:
once for each fault a cell can have (a step that leaves its state
unchanged, half of the batch left out, an answer altered where it is
produced). The cells run on one chip, so no exchange between chips exists
to leave out."""

import jax.numpy as jnp
import numpy as np
import pytest

import small


def run(name, **kw):
    cell = small.small_cell(name, 10)
    return small.run_small(cell, seed=2**31 + 29, seconds=0.5, **kw)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("name", ["mine.build_query", "mine.query"])
def test_mining_fault_is_caught(monkeypatch, name, fault):
    from repro.core import sketches as SK
    from repro.engine import engine as E

    if fault == "state_unchanged":
        # the build returns the empty sketch it started from
        monkeypatch.setattr(SK, "build_bloom",
                            lambda g, words, *a, **k: jnp.zeros(
                                (g.n, words), jnp.uint32))
    elif fault == "half_batch":
        real = E.edge_cardinalities

        def half(graph, sketch, plan, edges=None):
            cards = real(graph, sketch, plan, edges)
            return cards.at[cards.shape[0] // 2:].set(0.0)
        monkeypatch.setattr(E, "edge_cardinalities", half)
    else:
        real = E.MiningSession.edge_cardinalities

        def altered(self):
            return real(self).at[0].add(1.0)
        monkeypatch.setattr(E.MiningSession, "edge_cardinalities", altered)
    assert run(name)["correct"] is False


def test_mining_control_is_not_correct():
    from pgbench import control

    cell = small.small_cell("mine.build_query", 10)
    nums = control.control_numbers(cell, 2**31 + 31)
    assert any(nums[k] > v for k, v in cell.limits.items()), nums

