"""Graph model regressions: empty graphs, generator edge distributions,
canonical edge identity."""
import numpy as np
import pytest

from repro.core import graph as G
from repro.stream.dynamic_graph import DynamicGraph


def test_from_edge_array_n0_returns_valid_empty_graph():
    # regression: the dedupe key lo*n+hi used to divide by n on the way out
    g = G.from_edge_array(0, np.zeros((0, 2), dtype=np.int64))
    assert g.n == 0 and g.m == 0
    assert np.asarray(g.indptr).shape == (1,)
    assert np.asarray(g.edges).shape == (0, 2)
    assert np.asarray(g.deg).shape == (0,)
    assert g.adj.shape == (0, 1) and g.d_max == 1


def test_from_edge_array_n0_drops_out_of_range_edges():
    g = G.from_edge_array(0, np.array([[0, 1], [1, 0]]))
    assert g.n == 0 and g.m == 0


def test_from_edge_array_no_valid_edges():
    g = G.from_edge_array(5, np.array([[2, 2], [3, 3]]))   # only self loops
    assert g.n == 5 and g.m == 0
    assert np.asarray(g.deg).sum() == 0


def test_erdos_renyi_empty_cases():
    assert G.erdos_renyi(0, 0.5).m == 0
    assert G.erdos_renyi(1, 0.5).m == 0
    assert G.erdos_renyi(100, 0.0).m == 0


def test_triu_unrank_exhaustive():
    for n in (2, 3, 7, 40):
        iu = np.triu_indices(n, k=1)
        u, v = G._triu_unrank(np.arange(n * (n - 1) // 2), n)
        assert np.array_equal(u, iu[0]) and np.array_equal(v, iu[1])


def test_erdos_renyi_large_n_geometric_skipping():
    # n chosen so max_pairs > 4M triggers the sparse branch; the old
    # with-replacement sampler silently dropped duplicates/self-loops and
    # undershot p — geometric skipping realizes Binomial(max_pairs, p)
    n, p = 3000, 0.0005
    max_pairs = n * (n - 1) // 2
    assert max_pairs > 4_000_000
    counts = [G.erdos_renyi(n, p, seed=s).m for s in range(3)]
    mean, sigma = p * max_pairs, np.sqrt(p * (1 - p) * max_pairs)
    for m in counts:
        assert abs(m - mean) < 6 * sigma, (m, mean, sigma)
    g = G.erdos_renyi(n, p, seed=0)
    e = np.asarray(g.edges)
    assert (e[:, 0] < e[:, 1]).all()                 # canonical, no self loops
    assert e[:, 1].max() < n


# ---------------------------------------------------------------------------
# canonical_edge_keys: the edge identity from_edge_array and DynamicGraph share
# ---------------------------------------------------------------------------

def test_canonical_edge_keys_drops_self_loops():
    keys = G.canonical_edge_keys(10, np.array([[3, 3], [2, 5], [7, 7], [0, 0]]))
    np.testing.assert_array_equal(keys, [2 * 10 + 5])


def test_canonical_edge_keys_drops_out_of_range_endpoints():
    raw = np.array([[-1, 4], [4, -1], [2, 10], [10, 2], [11, 12], [1, 9]])
    np.testing.assert_array_equal(G.canonical_edge_keys(10, raw), [1 * 10 + 9])


def test_canonical_edge_keys_folds_both_directions():
    """u→v and v→u are one key, lo·n + hi, and keys come out sorted and
    unique whatever the input order and multiplicity."""
    raw = np.array([[6, 1], [1, 6], [6, 1], [4, 2], [2, 4], [0, 3]])
    np.testing.assert_array_equal(G.canonical_edge_keys(7, raw),
                                  [0 * 7 + 3, 1 * 7 + 6, 2 * 7 + 4])


@pytest.mark.parametrize("n,edges", [(0, np.array([[0, 1], [1, 0]])),
                                     (5, None)])
def test_canonical_edge_keys_empty(n, edges):
    keys = G.canonical_edge_keys(n, edges)
    assert keys.shape == (0,) and keys.dtype == np.int64


def _raw_edges(seed, n=60, m=400):
    """Random endpoints with duplicates, both directions, self loops and
    out-of-range ids mixed in."""
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2))
    e = np.concatenate([e, e[:50, ::-1], e[:30], np.stack([np.arange(10)] * 2, 1),
                        [[n, 1], [-2, 3]]])
    return n, e[rng.permutation(e.shape[0])]


def _edge_set(edges):
    return {tuple(int(x) for x in row) for row in np.asarray(edges)}


@pytest.mark.parametrize("via", ["from_edges", "apply_delta"])
def test_static_and_streaming_graphs_agree_on_edges(via):
    """``from_edge_array`` and ``DynamicGraph`` read the same raw edges as
    the same canonical edge set, and the same degrees and rows."""
    n, raw = _raw_edges(3)
    g = G.from_edge_array(n, raw)
    if via == "from_edges":
        dyn = DynamicGraph.from_edges(n, raw)
    else:
        dyn = DynamicGraph.from_edges(n, None)
        assert dyn.m == 0
        dyn.apply_delta(inserts=raw)
    assert _edge_set(dyn.edge_array()) == _edge_set(g.edges)
    assert dyn.m == g.m
    np.testing.assert_array_equal(dyn.deg, np.asarray(g.deg))
    for v in range(n):
        np.testing.assert_array_equal(dyn.neighbors(v), G.neighbors_np(g, v))
