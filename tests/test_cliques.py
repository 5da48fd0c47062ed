"""4-clique counting over the degree-ordered triangle list: the list against
brute force, each triple-intersection kind against a plain reference, the
Bloom-closing variant, and the session cache across stream deltas."""
import itertools
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as eng
from repro.core import graph as G, sketches as S
from repro.core.algorithms import cliques as CL
from repro.stream import ErrorBudgetPolicy, stream_session

GRAPHS = {
    "er": lambda: G.erdos_renyi(70, 0.18, seed=5),
    "kron": lambda: G.kronecker(8, 10, seed=4),
    # a circulant 6-regular graph: every degree tied, ids break each tie
    "regular": lambda: G.from_edge_array(48, np.array(
        [(v, (v + s) % 48) for v in range(48) for s in (1, 5, 11)])),
    "ba": lambda: G.barabasi_albert(90, 4, seed=2),
}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def g(request):
    return GRAPHS[request.param]()


def _neighbour_sets(g):
    nbrs = [set() for _ in range(g.n)]
    for u, v in np.asarray(g.edges).tolist():
        nbrs[u].add(v)
        nbrs[v].add(u)
    return nbrs


def _rank(g):
    deg = np.asarray(g.deg)
    rank = np.empty(g.n, np.int64)
    rank[np.lexsort((np.arange(g.n), deg))] = np.arange(g.n)
    return rank


def brute_triangles(g):
    """Every triangle as a tuple sorted by (degree, id) rank."""
    nbrs, rank = _neighbour_sets(g), _rank(g)
    out = set()
    for u, v in np.asarray(g.edges).tolist():
        for w in nbrs[u] & nbrs[v]:
            out.add(tuple(sorted((u, v, w), key=lambda x: rank[x])))
    return out


def listed(tris, count):
    rows = np.asarray(tris)
    assert not rows[count:].any()            # zero rows past T
    return [tuple(r) for r in rows[:count].tolist()]


def test_oriented_csr_ranks_rows_and_bound(g):
    oindptr, osrc, odst = (np.asarray(x) for x in
                           G.degree_oriented_csr(g.indptr, g.indices))
    rank = _rank(g)
    assert osrc.shape == odst.shape == (g.m,)
    assert (rank[osrc] < rank[odst]).all()
    want = {(min(u, v, key=lambda x: rank[x]), max(u, v, key=lambda x: rank[x]))
            for u, v in np.asarray(g.edges).tolist()}
    assert set(zip(osrc.tolist(), odst.tolist())) == want
    for v in range(g.n):
        row = odst[oindptr[v]:oindptr[v + 1]]
        assert (osrc[oindptr[v]:oindptr[v + 1]] == v).all()
        assert (np.diff(row) > 0).all()
    assert np.diff(oindptr).max() <= math.isqrt(2 * g.m)


@pytest.mark.parametrize("length", [1, 1000, 1024, 1025, 4099, (1 << 20) + 3])
def test_running_matches_numpy(length):
    x = np.random.default_rng(length).integers(-50, 50, length).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(G.running(x)), np.cumsum(x))
    np.testing.assert_array_equal(np.asarray(G.running(x, "max")),
                                  np.maximum.accumulate(x))


def test_triangle_list_equals_brute_force(g):
    tris, count, wedges = CL.triangle_list(g)
    got = listed(tris, count)
    assert len(got) == len(set(got)) == count
    assert set(got) == brute_triangles(g)
    assert tris.shape[0] == eng.pow2_bucket(count)
    oindptr, _, odst = G.degree_oriented_csr(g.indptr, g.indices)
    outdeg = np.diff(np.asarray(oindptr))
    assert wedges == int(outdeg[np.asarray(odst)].sum())


def test_session_triangles_cached_in_rank_order(g):
    sess = eng.session(g, "bf", storage_budget=2.0)
    tris, count = sess.triangles()
    assert sess.triangles()[0] is tris
    rank = _rank(g)
    rows = np.asarray(tris)[:count]
    assert (rank[rows[:, 0]] < rank[rows[:, 1]]).all()
    assert (rank[rows[:, 1]] < rank[rows[:, 2]]).all()


def test_exact_four_cliques_match_bruteforce(g):
    want = G.four_clique_count_bruteforce(g)
    assert float(CL.four_clique_count(g)) == want
    assert float(eng.session(g, None).four_clique_count()) == want


def test_bf_four_cliques_match_numpy_reference(g):
    sk = S.build(g, "bf", 2.0, num_hashes=2, seed=1)
    rows = np.asarray(sk.data)
    tris = np.array(sorted(brute_triangles(g)), dtype=np.int64).reshape(-1, 3)
    ones = np.bitwise_count(rows[tris[:, 0]] & rows[tris[:, 1]]
                            & rows[tris[:, 2]]).sum(axis=1, dtype=np.int64)
    bits = rows.shape[1] * 32
    est = -(bits / 2) * np.log1p(-np.minimum(ones, bits - 1) / bits)
    want = est.sum() / 4.0
    got = float(eng.session(g, sk).four_clique_count())
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_bf_popcount_total_is_exact(g):
    """``return_ones`` gives the exact Σ popcount(B_a & B_b & B_c) over the
    list, beside the same count."""
    sk = S.build(g, "bf", 2.0, num_hashes=2, seed=1)
    rows = np.asarray(sk.data)
    tris = np.array(sorted(brute_triangles(g)), dtype=np.int64).reshape(-1, 3)
    want = int(np.bitwise_count(rows[tris[:, 0]] & rows[tris[:, 1]]
                                & rows[tris[:, 2]]).sum())
    sess = eng.session(g, sk)
    cc4, ones = sess.four_clique_count(return_ones=True)
    hi, lo = np.asarray(ones).tolist()
    assert hi << 32 | lo == want
    assert float(cc4) == float(sess.four_clique_count())


def test_popcount_total_carries_past_32_bits(monkeypatch):
    """The low word's wrap carries into the high word: 16 triangles of
    popcount 2**30 + t sum to 2**34 + 120."""
    g = G.erdos_renyi(20, 0.3, seed=1)
    sk = S.build(g, "bf", 2.0, num_hashes=2, seed=1)
    monkeypatch.setattr(eng, "triple_cardinality_ones",
                        lambda sketch, t, plan: (1 << 30) + t[:, 0])
    tris = jnp.stack([jnp.arange(16, dtype=jnp.int32)] * 3, axis=1)
    _, ones = CL._bloom_triple_sums.__wrapped__(
        sk, tris, jnp.int32(16), plan=eng.EnginePlan(), chunk=2)
    hi, lo = np.asarray(ones).tolist()
    assert hi << 32 | lo == (1 << 34) + 120


@pytest.mark.parametrize("chunk", [8, 64, 1 << 16])
def test_bf_kernel_and_jnp_paths_bit_identical(g, chunk):
    sk = S.build(g, "bf", 2.0, num_hashes=2, seed=1)
    plan = eng.EnginePlan(edge_chunk=chunk)
    plain = float(CL.four_clique_count(g, sk, plan=plan))
    kern = float(CL.four_clique_count(g, sk, plan=plan.with_(use_kernel=True)))
    assert plain == kern


def test_bloom_closing_runs_on_the_same_enumeration(g):
    """Bloom-membership closing lists the exact triangles plus false
    positives, all from the same oriented wedges a→b→c."""
    sk = S.build(g, "bf", 0.5, num_hashes=2, seed=1)
    exact = set(listed(*CL.triangle_list(g)[:2]))
    tris, count, wedges = CL.triangle_list(g, bloom=sk)
    loose = listed(tris, count)
    assert wedges == CL.triangle_list(g)[2]
    assert len(loose) == len(set(loose)) and exact <= set(loose)
    nbrs, rank = _neighbour_sets(g), _rank(g)
    for a, b, c in loose:
        assert b in nbrs[a] and c in nbrs[b] and rank[a] < rank[b] < rank[c]
        if (a, b, c) not in exact:           # closed by a Bloom false positive
            assert c not in nbrs[a]
    # the count sums the AND estimates over exactly that list
    want = float(CL.four_clique_count(g, sk, triangles=(tris, count)))
    assert float(CL.four_clique_count(g, sk, exact_closing_test=False)) == want
    assert float(eng.session(g, sk).four_clique_count(
        exact_closing_test=False)) == want


def test_khash_four_cliques_over_the_list(g):
    sk = S.build(g, "kh", 2.0, seed=1)
    got = float(CL.four_clique_count(g, sk))
    assert np.isfinite(got) and got >= 0.0
    want = float(CL.four_clique_count(g))
    if want:
        assert abs(got - want) / want < 1.0


def test_triangle_cache_shared_by_fork_dropped_by_refresh(g):
    sess = eng.session(g, "bf", storage_budget=2.0)
    tris = sess.triangles()
    twin = sess.fork()
    assert twin.triangles() is tris
    twin.refresh(g, carry_index=None)
    assert twin._triangles is None and sess.triangles() is tris


def test_stream_delta_count_equals_fresh_session():
    g = G.erdos_renyi(60, 0.2, seed=9)
    st = stream_session(g, "bf", words=4, num_hashes=2, seed=3,
                        policy=ErrorBudgetPolicy(0.0))
    before = float(st.four_clique_count())
    rng = np.random.default_rng(1)
    pairs = np.array(list(itertools.combinations(range(12), 2)))
    st.apply_delta(pairs[rng.permutation(len(pairs))[:30]],
                   np.asarray(g.edges)[:5])
    after = float(st.four_clique_count())
    fresh_graph = G.from_edge_array(g.n, st.dyn.edge_array())
    fresh = eng.session(fresh_graph, st.sketch, plan=st.session.plan)
    assert after == float(fresh.four_clique_count())
    assert after != before
    assert float(eng.session(fresh_graph, None).four_clique_count()) \
        == G.four_clique_count_bruteforce(fresh_graph)


def test_four_cliques_reject_other_kinds():
    g = G.erdos_renyi(20, 0.3, seed=1)
    with pytest.raises(ValueError, match="sketch kind"):
        CL.four_clique_count(g, S.build(g, "kmv", 0.5, seed=1))


@pytest.mark.parametrize("kind", ["exact", "kh"])
@pytest.mark.parametrize("option", [{"return_ones": True},
                                    {"exact_closing_test": False}])
def test_bloom_only_options_reject_other_kinds(kind, option):
    g = G.erdos_renyi(20, 0.3, seed=1)
    sk = None if kind == "exact" else S.build(g, kind, 2.0, seed=1)
    with pytest.raises(ValueError, match="need a Bloom sketch"):
        CL.four_clique_count(g, sk, **option)

