"""Sketch construction: determinism, np/jax twins, membership semantics."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import graph as G, sketches as S
from repro.core.hashing import (fmix32, hash_u32, hash_unit_interval,
                                np_fmix32, np_hash_u32)
from repro.stream.dynamic_graph import DynamicGraph


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


ROW_GRAPHS = {"kronecker": lambda: G.kronecker(8, 8, seed=4),
              "erdos_renyi": lambda: G.erdos_renyi(200, 0.06, seed=9),
              "star": _star}


@pytest.mark.parametrize("graph_name", list(ROW_GRAPHS))
@pytest.mark.parametrize("kind", ["kh", "1h", "kmv"])
def test_row_builders_equal_build_rows(kind, graph_name):
    """The invariant behind the dirty-row rebuilds: the per-row builder on
    a subset of vertices at a ``DynamicGraph``'s capacity, wider than the
    graph's ``d_max``, gives those rows of ``build(graph, kind)``. The
    subset holds the largest row (in the star it fills ``d_max``: no pad
    in the build, pads in the rebuild) and the smallest."""
    g = ROW_GRAPHS[graph_name]()
    k, seed = 8, 3
    full = np.asarray(S.build(g, kind, k=k, seed=seed).data)
    row_fn = {"kh": S.khash_rows, "1h": S.onehash_rows, "kmv": S.kmv_rows}[kind]
    deg = np.asarray(g.deg)
    rng = np.random.default_rng(len(graph_name))
    verts = np.concatenate([[np.argmax(deg), np.argmin(deg)],
                            rng.choice(g.n, 16, replace=False)])
    assert deg[verts[0]] == g.d_max
    wide = DynamicGraph.from_graph(g).adj
    assert wide.shape[1] > g.d_max
    rebuild = jax.jit(row_fn, static_argnames=("n", "k", "seed"))
    got = np.asarray(rebuild(jnp.asarray(wide[verts]), n=g.n, k=k, seed=seed))
    np.testing.assert_array_equal(got, full[verts])


TWIN_SEEDS = [0, 1, 12345, 2**31 - 1]


def _uint32_keys(seed):
    """The uint32 corners and 4,096 seeded keys over the whole range."""
    rng = np.random.default_rng(seed)
    corners = np.array([0, 1, 2**31 - 1, 2**31, 2**32 - 1], np.uint32)
    return np.concatenate([corners, rng.integers(0, 2**32, size=4096,
                                                 dtype=np.uint32)])


@pytest.mark.parametrize("seed", TWIN_SEEDS)
def test_fmix32_host_twin_bit_identical(seed):
    """``np_fmix32`` is ``fmix32`` on the host, bit for bit, over the whole
    uint32 range; the finalizer is a bijection, so distinct keys stay
    distinct."""
    x = _uint32_keys(seed)
    got = np.asarray(fmix32(jnp.asarray(x)))
    assert np.array_equal(got, np_fmix32(x))
    assert np.unique(got).size == np.unique(x).size


@pytest.mark.parametrize("seed", TWIN_SEEDS)
def test_hash_u32_host_twin_bit_identical(seed):
    """``np_hash_u32`` is ``hash_u32`` on the host at the same seed, for
    uint32 keys and for the int32 vertex ids the sketch builders pass:
    the host builds and the benchmark's numpy reference set the bits the
    device does."""
    x = _uint32_keys(seed)
    assert np.array_equal(np.asarray(hash_u32(jnp.asarray(x), seed)),
                          np_hash_u32(x, seed))
    ids = (x % np.uint32(2**31)).astype(np.int32)
    assert np.array_equal(np.asarray(hash_u32(jnp.asarray(ids), seed)),
                          np_hash_u32(ids, seed))


@pytest.mark.parametrize("seed", [0, 7])
def test_hash_unit_interval_is_scaled_hash(seed):
    """KMV's hash: (h + 1) / 2**32 in float32 for the seeded 32-bit hash
    h, so every value lies in (0, 1] and the order of values follows the
    order of hashes."""
    x = _uint32_keys(seed)
    got = np.asarray(hash_unit_interval(jnp.asarray(x), seed))
    h = np_hash_u32(x, seed)
    want = (h.astype(np.float32) + np.float32(1)) * np.float32(2.0 ** -32)
    assert got.dtype == np.float32
    assert np.array_equal(got, want)
    assert np.all(got > 0) and np.all(got <= 1)
    order = np.argsort(h, kind="stable")
    assert np.all(np.diff(got[order]) >= 0)


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
