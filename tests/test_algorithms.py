"""Graph algorithms: exact oracles + estimator accuracy on fixed seeds."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import graph as G, sketches as S, exact as X
from repro.core import (triangle_count, four_clique_count, jarvis_patrick,
                        pair_similarity, link_prediction_effectiveness)
from repro.core.algorithms.clustering import (_connected_components,
                                             _keep_mask, _kept_components,
                                             _kept_first)
from repro.core.algorithms.linkpred import split_edges
from repro.core.algorithms.similarity import similarity_from_cardinalities
from repro.core.algorithms.tc import local_clustering_coefficient
from repro.engine import pow2_bucket


@pytest.fixture(scope="module")
def g():
    return G.erdos_renyi(250, 0.06, seed=11)


@pytest.fixture(scope="module")
def gk():
    return G.kronecker(9, 12, seed=4)


def test_exact_tc_matches_dense_oracle(g):
    assert int(X.exact_triangle_count(g)) == G.triangle_count_dense(g)


def test_exact_tc_chunked_fold(g):
    full = int(X.exact_triangle_count(g))
    chunked = int(X.exact_triangle_count(g, edge_chunk=64))
    assert full == chunked


def test_exact_4clique_matches_bruteforce(g):
    assert int(four_clique_count(g)) == G.four_clique_count_bruteforce(g)


def test_tc_estimators_accuracy(gk):
    tc = int(X.exact_triangle_count(gk))
    for kind, tol in [("bf", 0.8), ("kh", 0.35), ("1h", 0.45)]:
        sk = S.build(gk, kind, storage_budget=0.33, num_hashes=1, seed=2)
        est = float(triangle_count(gk, sk))
        assert abs(est - tc) / tc < tol, (kind, est, tc)


def test_tc_kernel_path_equals_jnp(g):
    sk = S.build(g, "bf", 0.33, num_hashes=2, seed=1)
    a = float(triangle_count(g, sk))
    b = float(triangle_count(g, sk, use_kernel=True))
    assert abs(a - b) < 1e-3


def test_clustering_threshold_monotone(g):
    _, n_lo = jarvis_patrick(g, None, "common", 1.0)
    _, n_hi = jarvis_patrick(g, None, "common", 6.0)
    # higher threshold keeps fewer edges -> at least as many clusters
    assert int(n_hi) >= int(n_lo)


def test_clustering_sketch_count_within_paper_band():
    """Cluster-count ratio vs exact stays inside the paper's own plotted
    band (Fig. 7 caps relative cluster counts at 10; threshold clustering is
    the documented high-variance case of the AND estimator, §VIII-C)."""
    gp = G.random_bipartite_community(300, 4, 0.25, 0.002, seed=5)
    _, n_exact = jarvis_patrick(gp, None, "jaccard", 0.05)
    for kind, b in [("bf", 2), ("kh", 0)]:
        sk = S.build(gp, kind, 0.5, num_hashes=max(b, 1), seed=3)
        _, n_sk = jarvis_patrick(gp, sk, "jaccard", 0.05)
        hi, lo = max(int(n_sk), int(n_exact)), max(min(int(n_sk), int(n_exact)), 1)
        assert hi / lo < 10.0, (kind, int(n_exact), int(n_sk))


def test_clustering_planted_partition():
    g = G.random_bipartite_community(300, 4, 0.25, 0.002, seed=5)
    labels, num = jarvis_patrick(g, None, "common", 2.0)
    # strong communities: far fewer clusters than vertices
    assert int(num) < g.n // 3


def _label_propagation_np(n, edges, keep, max_iters):
    """numpy replay of ``_connected_components``' capped synchronous loop:
    scatter-min over the kept edges, one pointer jump, stop when unchanged."""
    u, v = edges[:, 0], edges[:, 1]
    labels, it, changed = np.arange(n, dtype=np.int32), 0, True
    while changed and it < max_iters:
        lu, lv = labels[u], labels[v]
        low = np.minimum(lu, lv)
        new = labels.copy()
        np.minimum.at(new, u, np.where(keep, low, lu))
        np.minimum.at(new, v, np.where(keep, low, lv))
        new = new[new]
        changed = bool((new != labels).any())
        labels, it = new, it + 1
    return labels, it


def _component_min_np(n, edges, keep):
    """Union-find over the kept edges, each root the least id of its set."""
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for (a, b), k in zip(edges.tolist(), keep.tolist()):
        if k:
            ra, rb = find(a), find(b)
            parent[max(ra, rb)] = min(ra, rb)
    return np.array([find(x) for x in range(n)], dtype=np.int32)


def _random_edges(n, m, seed):
    rng = np.random.default_rng(seed)
    e = np.sort(rng.integers(0, n, size=(m, 2)), axis=1)
    return np.unique(e[e[:, 0] < e[:, 1]], axis=0).astype(np.int32)


def _path_edges(n, seed=None):
    """A path through all n vertices, in id order or (with a seed) in a
    shuffled order."""
    order = (np.arange(n) if seed is None
             else np.random.default_rng(seed).permutation(n))
    return np.sort(np.stack([order[:-1], order[1:]], axis=1),
                   axis=1).astype(np.int32)


_CC_CASES = {
    # name: (n, edges, keep, max_iters); keep as ``_cc_keep`` reads it
    "random_keep_all": (300, _random_edges(300, 260, 1), None, 200),
    "random_keep_none": (300, _random_edges(300, 260, 1), False, 200),
    "random_keep_random": (300, _random_edges(300, 400, 2), "random", 200),
    "random_keep_last": (300, _random_edges(300, 260, 1), "last", 200),
    "isolated_vertex": (7, np.array([[0, 1], [1, 2], [2, 4], [5, 6]],
                                    np.int32), None, 200),
    "long_path": (1500, _path_edges(1500), None, 200),
    "shuffled_path": (256, _path_edges(256, 3), None, 200),
    "shuffled_path_keep_sparse": (1500, _path_edges(1500, 3), "sparse", 200),
    "pow2_path": (257, _path_edges(257, 6), None, 200),
    "capped_early": (1500, _path_edges(1500, 3), None, 3),
}


def _cc_keep(keep, m):
    """bool[m] from a case's keep: None all, False none, "random" 60 % and
    "sparse" 30 % of the edges (seeded), "last" the last 10 edges."""
    if keep is None:
        return np.ones(m, bool)
    if keep is False:
        return np.zeros(m, bool)
    if keep == "last":
        return np.arange(m) >= m - 10
    share = {"random": 0.6, "sparse": 0.3}[keep]
    return np.random.default_rng(4).random(m) < share


@pytest.mark.parametrize("case", sorted(_CC_CASES))
def test_label_propagation_matches_numpy(case):
    n, edges, keep, max_iters = _CC_CASES[case]
    keep = _cc_keep(keep, edges.shape[0])
    labels, num, iters = _connected_components(
        n, jnp.asarray(edges), jnp.asarray(keep), max_iters=max_iters)
    want, want_iters = _label_propagation_np(n, edges, keep, max_iters)
    labels = np.asarray(labels)
    assert labels.dtype == np.int32
    np.testing.assert_array_equal(labels, want)
    assert int(iters) == want_iters
    assert int(num) == int(np.sum(want == np.arange(n)))
    if case == "capped_early":
        assert want_iters == max_iters
        assert (labels != _component_min_np(n, edges, keep)).any()
    else:
        assert want_iters < max_iters
        np.testing.assert_array_equal(labels,
                                      _component_min_np(n, edges, keep))
    if case == "long_path":
        # scatter-min alone moves a label one hop a round; the pointer jump
        # crosses the 1,499-edge chain in a few dozen
        assert 2 < want_iters < 64


# the loop's rows for cases that pin each regime of min(pow2_bucket(kept), m)
_CC_ROWS = {
    "random_keep_none": 1,             # kept 0
    "random_keep_all": 259,            # kept 259 = m: 512 clamped to m
    "pow2_path": 256,                  # kept 256 = m = pow2_bucket(256)
    "random_keep_random": 256,         # kept 217 < 256 < m 399
    "random_keep_last": 16,            # kept 10 < 16 < m 259
    "shuffled_path_keep_sparse": 512,  # kept 450 < 512 < m 1,499
}


@pytest.mark.parametrize("case", sorted(_CC_CASES))
def test_kept_prefix_equals_full_edge_loop(case):
    """``jarvis_patrick``'s loop path (kept-first sort, host count, loop on
    a pow2 prefix) gives the labels, cluster count and iteration count of
    the loop over all edges and of the numpy replay; the non-kept rows
    inside the prefix are no-ops."""
    n, edges, keep, max_iters = _CC_CASES[case]
    m = edges.shape[0]
    keep = _cc_keep(keep, m)
    kept = int(keep.sum())
    sorted_edges, sorted_keep, count = _kept_first(jnp.asarray(edges),
                                                   jnp.asarray(keep))
    # stable: kept edges first, each group in its canonical order
    np.testing.assert_array_equal(np.asarray(sorted_edges),
                                  np.concatenate([edges[keep], edges[~keep]]))
    np.testing.assert_array_equal(np.asarray(sorted_keep), np.arange(m) < kept)
    assert int(count) == kept

    labels, num, iters, got_kept, rows = _kept_components(
        n, jnp.asarray(edges), jnp.asarray(keep), max_iters)
    assert got_kept == kept
    assert rows == min(pow2_bucket(kept), m)
    assert kept <= rows <= m
    if case in _CC_ROWS:
        assert rows == _CC_ROWS[case]
    full_labels, full_num, full_iters = _connected_components(
        n, jnp.asarray(edges), jnp.asarray(keep), max_iters=max_iters)
    want, want_iters = _label_propagation_np(n, edges, keep, max_iters)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(full_labels))
    np.testing.assert_array_equal(np.asarray(labels), want)
    assert int(iters) == int(full_iters) == want_iters
    assert int(num) == int(full_num) == int(np.sum(want == np.arange(n)))


@pytest.mark.parametrize("similarity,threshold",
                         [("common", 2.0), ("jaccard", 0.05),
                          ("overlap", 0.3), ("total", 9.0)])
@pytest.mark.parametrize("kind", ["exact", "bf"])
def test_keep_mask_matches_eager_scoring(g, kind, similarity, threshold):
    from repro import engine as eng
    sk = None if kind == "exact" else S.build(g, "bf", 0.33, num_hashes=2,
                                              seed=1)
    cards = eng.session(g, sk).edge_cardinalities()
    du = jnp.take(g.deg, g.edges[:, 0]).astype(jnp.float32)
    dv = jnp.take(g.deg, g.edges[:, 1]).astype(jnp.float32)
    want = similarity_from_cardinalities(cards, du, dv, similarity) >= threshold
    got = _keep_mask(g.deg, g.edges, cards, jnp.asarray(threshold, jnp.float32),
                     similarity)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0 < int(jnp.sum(got)) < g.m or similarity == "total"


@pytest.mark.parametrize("similarity,threshold",
                         [("common", 2.0), ("jaccard", 0.05), ("overlap", 0.3)])
def test_jarvis_patrick_under_outer_jit_matches_eager(g, similarity,
                                                      threshold):
    sk = S.build(g, "bf", 0.33, num_hashes=2, seed=1)
    labels, num = jarvis_patrick(g, sk, similarity, threshold)
    fn = jax.jit(functools.partial(jarvis_patrick, similarity=similarity,
                                   threshold=threshold))
    jl, jn = fn(g, sk)
    np.testing.assert_array_equal(np.asarray(jl), np.asarray(labels))
    assert int(jn) == int(num)


def test_similarity_measures_exact(g):
    pairs = g.edges[:64]
    du = np.asarray(g.deg)[np.asarray(pairs)[:, 0]].astype(float)
    dv = np.asarray(g.deg)[np.asarray(pairs)[:, 1]].astype(float)
    inter = np.asarray(X.exact_pair_cardinalities(g, pairs)).astype(float)
    jac = np.asarray(pair_similarity(g, pairs, "jaccard"))
    np.testing.assert_allclose(jac, inter / np.maximum(du + dv - inter, 1.0), rtol=1e-5)
    tot = np.asarray(pair_similarity(g, pairs, "total"))
    np.testing.assert_allclose(tot, du + dv - inter, rtol=1e-5)


def test_adamic_adar_bf_vs_exact(g):
    pairs = g.edges[:64]
    aa_exact = np.asarray(pair_similarity(g, pairs, "adamic_adar"))
    sk = S.build(g, "bf", 0.5, num_hashes=2, seed=3)
    aa_bf = np.asarray(pair_similarity(g, pairs, "adamic_adar", sk))
    # BF membership has no false negatives: BF estimate >= exact - tiny
    assert np.all(aa_bf >= aa_exact - 1e-4)
    # and inflation stays bounded on this budget
    assert np.mean(aa_bf - aa_exact) < 2.0


def test_local_clustering_coefficient(g):
    cc = np.asarray(local_clustering_coefficient(g))
    assert cc.shape == (g.n,)
    assert np.all(cc >= 0) and np.all(cc <= 1.0 + 1e-6)


def test_link_prediction_beats_random(gk):
    ef = link_prediction_effectiveness(gk, "common", removed_fraction=0.05, seed=3)
    # wedge-candidate common-neighbors must beat uniform-random guessing
    assert ef > 0.01


def test_link_prediction_with_sketch(gk):
    ef = link_prediction_effectiveness(gk, "common", removed_fraction=0.05,
                                       sketch_kind="bf", storage_budget=0.5, seed=3)
    assert ef > 0.005


# ---------------------------------------------------------------------------
# exact intersection elements (Adamic-Adar / Resource-Allocation oracle)
# ---------------------------------------------------------------------------

ELEMENT_GRAPHS = {"erdos_renyi": lambda: G.erdos_renyi(250, 0.06, seed=11),
                  "kronecker": lambda: G.kronecker(9, 12, seed=4),
                  "barabasi_albert": lambda: G.barabasi_albert(300, 5, seed=2)}


@pytest.mark.parametrize("name", list(ELEMENT_GRAPHS))
def test_exact_pair_intersection_elements_match_numpy(name):
    """Each row holds exactly N_u ∩ N_v (``np.intersect1d``), padded with
    n, for edges, random pairs and u == u."""
    g = ELEMENT_GRAPHS[name]()
    rng = np.random.default_rng(5)
    pairs = np.concatenate([np.asarray(g.edges)[:200],
                            rng.integers(0, g.n, size=(100, 2)),
                            np.stack([np.arange(20)] * 2, axis=1)]).astype(np.int32)
    out = np.asarray(jax.jit(X.exact_pair_intersection_elements)(
        g, jnp.asarray(pairs)))
    assert out.shape == (pairs.shape[0], g.d_max)
    for (u, v), row in zip(pairs, out):
        assert np.all(row <= g.n)
        np.testing.assert_array_equal(
            np.sort(row[row < g.n]),
            np.intersect1d(G.neighbors_np(g, u), G.neighbors_np(g, v)))


# ---------------------------------------------------------------------------
# link prediction's edge split (paper Listing 5)
# ---------------------------------------------------------------------------

def test_split_edges_removes_the_fraction_asked(gk):
    for fraction in (0.05, 0.3):
        sparse, removed = split_edges(gk, fraction, seed=1)
        r = max(1, int(fraction * gk.m))
        assert removed.shape == (r, 2)
        assert sparse.m == gk.m - r and sparse.n == gk.n


def test_split_edges_partitions_the_edge_set(gk):
    """Removed and kept edges are disjoint and together are the graph's."""
    sparse, removed = split_edges(gk, 0.2, seed=2)
    kept = {tuple(e) for e in np.asarray(sparse.edges).tolist()}
    gone = {tuple(e) for e in removed.tolist()}
    assert len(gone) == removed.shape[0]
    assert not kept & gone
    assert kept | gone == {tuple(e) for e in np.asarray(gk.edges).tolist()}


def test_split_edges_is_seeded(gk):
    a_sparse, a_removed = split_edges(gk, 0.1, seed=7)
    b_sparse, b_removed = split_edges(gk, 0.1, seed=7)
    _, c_removed = split_edges(gk, 0.1, seed=8)
    np.testing.assert_array_equal(a_removed, b_removed)
    np.testing.assert_array_equal(np.asarray(a_sparse.edges),
                                  np.asarray(b_sparse.edges))
    assert not np.array_equal(a_removed, c_removed)
