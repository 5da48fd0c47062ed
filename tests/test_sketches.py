"""Sketch construction: determinism, np/jax twins, membership semantics."""
import functools

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import graph as G, sketches as S
from repro.core.hashing import hash_u32, np_hash_u32


def test_hash_np_jax_twins():
    xs = np.arange(1000, dtype=np.uint32)
    for seed in (0, 1, 12345):
        a = np.asarray(hash_u32(jnp.asarray(xs), seed))
        b = np_hash_u32(xs, seed)
        assert np.array_equal(a, b)


def test_hash_avalanche():
    xs = np.arange(4096, dtype=np.uint32)
    h = np_hash_u32(xs, 3)
    # bit balance: each output bit ~50% set
    bits = ((h[:, None] >> np.arange(32)[None, :]) & 1).mean(axis=0)
    assert np.all(bits > 0.45) and np.all(bits < 0.55)


@pytest.fixture(scope="module")
def g():
    return G.erdos_renyi(300, 0.05, seed=7)


def test_bloom_np_equals_jax(g):
    for b in (1, 2, 4):
        bf = S.build_bloom(g, words=8, num_hashes=b, seed=5)
        bf_np = S.build_bloom_np(g, words=8, num_hashes=b, seed=5)
        assert np.array_equal(np.asarray(bf), bf_np)


def _isolated_er():
    """Erdős-Rényi with isolated vertices (three at this seed)."""
    g = G.erdos_renyi(200, 0.02, seed=3)
    assert int(np.sum(np.asarray(g.deg) == 0)) > 0
    return g


def _hub_kronecker():
    """Scale-10 Kronecker: d_max 471 against a mean degree of about 21."""
    g = G.kronecker(10, 16, seed=2)
    assert g.d_max > 10 * 2 * g.m / g.n
    return g


def _headroom_view():
    """A ``graph_view`` whose adjacency carries 7 columns of headroom."""
    base = G.erdos_renyi(120, 0.05, seed=11)
    wide = G.from_edge_array(base.n, np.asarray(base.edges),
                             pad_to_max_degree=base.d_max + 7)
    return G.graph_view(base.n, base.m, wide.deg, wide.adj, wide.edges)


def _edgeless():
    return G.from_edge_array(40, np.zeros((0, 2), np.int64))


def _star():
    """Leaves 1..32 share one neighbour: consecutive rows set equal bits."""
    return G.from_edge_array(33, np.stack([np.zeros(32, np.int64),
                                           np.arange(1, 33)], axis=1))


BLOOM_GRAPHS = {"er_isolated": _isolated_er, "kronecker_hub": _hub_kronecker,
                "headroom_view": _headroom_view, "edgeless": _edgeless,
                "star": _star}


@pytest.fixture(scope="module", params=list(BLOOM_GRAPHS))
def bloom_graph(request):
    return BLOOM_GRAPHS[request.param]()


def _padded_bloom(g, words, b, seed):
    """The padded-adjacency build: ``bloom_rows`` over chunks of rows."""
    fn = functools.partial(S.bloom_rows, n=g.n, words=words, num_hashes=b,
                           seed=seed)
    return np.asarray(S._map_vertex_chunks(fn, g.adj, 64, (words,),
                                           jnp.uint32))


@pytest.mark.parametrize("seed", [0, 5])
@pytest.mark.parametrize("b", [1, 2, 3])
def test_edge_list_bloom_equals_padded_and_host(bloom_graph, b, seed):
    """The edge-list build sets the same bits as the padded-adjacency rows
    and the host build, bit for bit."""
    g = bloom_graph
    got = np.asarray(S.build_bloom(g, 8, b, seed))
    assert got.shape == (g.n, 8) and got.dtype == np.uint32
    assert np.array_equal(got, _padded_bloom(g, 8, b, seed))
    assert np.array_equal(got, S.build_bloom_np(g, 8, b, seed))


@pytest.mark.parametrize("b", [1, 3])
def test_edge_list_bloom_pair_sort_equals_key_sort(bloom_graph, b):
    """The lexicographic (row, position) sort, taken where n·32W does not
    fit an int32 key, sets the same bits as the one-key sort."""
    g = bloom_graph
    pair = S._bloom_from_edges(g.edges, n=g.n, words=8, num_hashes=b,
                               seed=5, one_key=False)
    assert np.array_equal(np.asarray(pair),
                          np.asarray(S.build_bloom(g, 8, b, 5)))


def test_bloom_membership_no_false_negatives(g):
    words, b, seed = 8, 2, 5
    bf = S.build_bloom(g, words, b, seed)
    total_bits = words * 32
    for v in [0, 5, 77]:
        nbrs = G.neighbors_np(g, v)
        if len(nbrs) == 0:
            continue
        got = S.bloom_membership(bf[v], jnp.asarray(nbrs), g.n, b, total_bits, seed)
        assert bool(np.all(np.asarray(got))), "bloom filters never have false negatives"


def test_khash_elements_are_neighbors(g):
    kh = np.asarray(S.build_khash(g, k=8, seed=3))
    for v in [1, 10, 100]:
        nbrs = set(G.neighbors_np(g, v).tolist())
        elems = set(int(e) for e in kh[v] if e < g.n)
        assert elems <= nbrs


def test_1hash_sorted_and_unique(g):
    oh = np.asarray(S.build_1hash(g, k=8, seed=3))
    hs = np.asarray(S.onehash_values(jnp.asarray(oh), g.n, 3))
    for v in range(0, g.n, 37):
        row_h = hs[v][oh[v] < g.n]
        assert np.all(np.diff(row_h.astype(np.int64)) >= 0)
        valid = oh[v][oh[v] < g.n]
        assert len(set(valid.tolist())) == len(valid)


def test_kmv_sorted_unit_interval(g):
    kv = np.asarray(S.build_kmv(g, k=8, seed=3))
    valid = kv[kv < 1.5]
    assert np.all(valid > 0) and np.all(valid <= 1.0)


def test_budget_sizing():
    n, m = 10_000, 200_000
    w = S.bloom_words_for_budget(n, m, 0.25)
    total_bits = n * w * 32
    csr_bits = (2 * m + n + 1) * 32
    assert total_bits <= 1.35 * 0.25 * csr_bits  # within rounding slack
    k = S.minhash_k_for_budget(n, m, 0.25)
    assert n * k <= 1.35 * 0.25 * (2 * m + n + 1)


def test_bloom_words_always_even():
    """Word counts round UP to a multiple of 2 (64-bit lanes) — including
    when the odd value comes from the min_words clamp, the case the old
    `words + (words % 2)` formulation leaked through."""
    for n, m, s, min_words in [
        (100, 300, 0.25, 2),     # budget-driven sizing
        (100, 300, 1e-6, 3),     # odd min_words clamp must still round up
        (100, 300, 1e-6, 1),
        (1000, 50_000, 0.33, 2),
        (17, 40, 0.5, 5),
    ]:
        w = S.bloom_words_for_budget(n, m, s, min_words=min_words)
        assert w % 2 == 0, (n, m, s, min_words, w)
        assert w >= min_words
    # round-up never shrinks below the budget-implied word count
    assert S.bloom_words_for_budget(100, 300, 0.25) >= 2


def test_pack_unpack_roundtrip(rng):
    bits = jnp.asarray(rng.random((5, 96)) < 0.3)
    packed = S.pack_bits(bits)
    assert packed.dtype == jnp.uint32
    assert np.array_equal(np.asarray(S.unpack_bits(packed)), np.asarray(bits))
