"""Batched mining engine: fused-pass kernels, plan routing, sessions."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import engine as eng
from repro.core import graph as G, sketches as S
from repro.core import triangle_count, four_clique_count, jarvis_patrick
from repro.core.algorithms.tc import local_clustering_coefficient
from repro.core.intersect import make_pair_cardinality_fn
from repro.distributed import sharding
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def g():
    return G.erdos_renyi(200, 0.07, seed=7)


@pytest.fixture(scope="module")
def sk(g):
    return S.build(g, "bf", 0.33, num_hashes=2, seed=1)


# ---------------------------------------------------------------------------
# fused-pass kernels vs the reference popcount path (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_e", [1, 8, 64])
@pytest.mark.parametrize("n,e,w", [(16, 40, 4), (100, 333, 18), (5, 9, 2),
                                   (64, 63, 6)])
def test_block_gather_edge_kernel(n, e, w, block_e, rng):
    bloom = jnp.asarray(rng.integers(0, 2**32, size=(n, w), dtype=np.uint32))
    edges = jnp.asarray(rng.integers(0, n, size=(e, 2), dtype=np.int32))
    out = ops.bf_edge_intersect(bloom, edges, block_e=block_e)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.bf_edge_intersect(bloom, edges)))


@pytest.mark.parametrize("block_e", [1, 8, 64])
@pytest.mark.parametrize("n,t,w", [(16, 40, 4), (50, 129, 10), (7, 3, 2)])
def test_block_gather_triple_kernel(n, t, w, block_e, rng):
    bloom = jnp.asarray(rng.integers(0, 2**32, size=(n, w), dtype=np.uint32))
    triples = jnp.asarray(rng.integers(0, n, size=(t, 3), dtype=np.int32))
    out = ops.bf_edge_intersect3(bloom, triples, block_e=block_e)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.bf_edge_intersect3(bloom, triples)))


def test_block_gather_ragged_word_axis(rng):
    # W not a multiple of block_w: wrapper must zero-pad the word axis
    bloom = jnp.asarray(rng.integers(0, 2**32, size=(30, 7), dtype=np.uint32))
    edges = jnp.asarray(rng.integers(0, 30, size=(21, 2), dtype=np.int32))
    out = ops.bf_edge_intersect(bloom, edges, block_e=8, block_w=4)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.bf_edge_intersect(bloom, edges)))


# ---------------------------------------------------------------------------
# plan / fold / layout
# ---------------------------------------------------------------------------

def test_fold_and_map_chunking_equivalence(g, sk):
    fn = eng.pair_cardinality_fn(g, sk, eng.EnginePlan())
    base = fn(g.edges)
    for chunk in (17, 64, 10**6):
        plan = eng.EnginePlan(edge_chunk=chunk)
        vals = eng.map_edges(g.edges, fn, plan)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(base), rtol=1e-6)
        total = eng.fold_edges(
            g.edges, lambda p, m: jnp.sum(jnp.where(m, fn(p), 0.0)), plan)
        np.testing.assert_allclose(float(total), float(jnp.sum(base)), rtol=1e-5)


@pytest.mark.parametrize("kind", [None, "bf", "kh", "1h", "kmv"])
def test_sum_edge_cardinalities_is_sum_of_cards(g, kind):
    """The TC numerator's fold, hub-ordered and in 64-edge chunks, equals
    the sum of the per-edge cardinalities in the graph's order (exactly
    for exact counts, to float32 rounding for sketch estimates)."""
    sketch = None if kind is None else S.build(g, kind, 0.33, num_hashes=2,
                                               seed=1, k=16)
    plan = eng.EnginePlan(edge_chunk=64, degree_order=True)
    assert g.m % 64 != 0
    total = float(eng.sum_edge_cardinalities(g, sketch, plan))
    cards = np.asarray(eng.edge_cardinalities(
        g, sketch, plan.with_(degree_order=False)), np.float64)
    assert cards.shape == (g.m,)
    if kind is None:
        assert total == cards.sum()
    else:
        np.testing.assert_allclose(total, cards.sum(), rtol=1e-5)


@pytest.fixture(scope="module")
def exact_cards(g):
    """The exact per-pair cardinality function and its masked chunk sum,
    built once so the folds of every case share their compiled scans."""
    fn = jax.jit(eng.pair_cardinality_fn(g, None, eng.EnginePlan()))

    def chunk_fn(pairs, mask):
        return jnp.sum(jnp.where(mask, fn(pairs), 0.0))

    return fn, chunk_fn


@pytest.mark.parametrize("m", [40, 3 * 64, 150])
def test_fold_edges_masked_equals_fold_edges(g, exact_cards, m):
    """On chunk-padded edges whose pad rows are real pairs, the masked fold
    counts only the rows its mask keeps: it equals ``fold_edges`` on the
    unpadded edges (m below one chunk, m = 3 chunks, m ragged)."""
    chunk = 64
    plan = eng.EnginePlan(edge_chunk=chunk)
    fn, chunk_fn = exact_cards
    edges = g.edges[:m]
    pad = (-m) % chunk
    padded = jnp.concatenate([edges, g.edges[m:m + pad]])
    mask = jnp.arange(m + pad) < m
    got = float(eng.fold_edges_masked(padded, mask, chunk_fn, plan))
    want = float(eng.fold_edges(edges, chunk_fn, plan))
    assert got == want == float(np.sum(np.asarray(fn(edges))))
    assert pad == 0 or float(np.sum(np.asarray(fn(padded)))) > want


@pytest.mark.parametrize("lo", [1, 8, 64])
def test_pow2_bucket_is_smallest_power_of_two(lo):
    """``pow2_bucket(size, lo)``, for the power-of-two floors its callers
    pass, is the smallest power of two that is at least ``max(size, lo)``:
    one shape per octave of sizes keeps padded batches from compiling a
    program per size."""
    for size in range(0, 5000):
        b = eng.pow2_bucket(size, lo)
        need = max(size, lo, 1)
        assert b & (b - 1) == 0 and b >= need and b // 2 < need, (size, lo, b)


def test_degree_order_is_a_permutation(g):
    edges_s, inv = eng.order_edges_by_hub(g, g.edges)
    # same multiset of edges, and inv restores the original order
    np.testing.assert_array_equal(np.asarray(jnp.take(edges_s, inv, axis=0)),
                                  np.asarray(g.edges))
    du = np.asarray(jnp.take(g.deg, edges_s[:, 0]))
    dv = np.asarray(jnp.take(g.deg, edges_s[:, 1]))
    hub_deg = np.maximum(du, dv)
    buckets = np.frexp(np.maximum(hub_deg, 1).astype(np.float32))[1]
    assert (np.diff(buckets) <= 0).all()          # hubs lead the schedule


def test_edge_cardinalities_order_invariant(g, sk):
    plain = eng.edge_cardinalities(g, sk, eng.EnginePlan(degree_order=False))
    ordered = eng.edge_cardinalities(g, sk, eng.EnginePlan(degree_order=True))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(ordered))


def test_resolve_plan_rejects_unknown_kwargs(g, sk):
    with pytest.raises(TypeError):
        eng.resolve_plan(None, g, sk, {"edge_chnk": 4})


def test_explicit_plan_survives_resolution(g, sk):
    plan = eng.EnginePlan(edge_chunk=256, block_e=4)
    assert eng.resolve_plan(plan, g, sk, {}) is plan
    # and four_clique_count must not override an explicit plan's chunking
    a = float(four_clique_count(g, sk, plan=eng.EnginePlan(edge_chunk=32)))
    b = float(four_clique_count(g, sk, plan=eng.EnginePlan(edge_chunk=10**6)))
    np.testing.assert_allclose(a, b, rtol=1e-5)


def test_kernel_ops_handle_empty_inputs(sk):
    out = ops.bf_edge_intersect(sk.data, jnp.zeros((0, 2), jnp.int32))
    assert out.shape == (0,) and out.dtype == jnp.int32
    out3 = ops.bf_edge_intersect3(sk.data, jnp.zeros((0, 3), jnp.int32))
    assert out3.shape == (0,)


# ---------------------------------------------------------------------------
# engine path vs the legacy per-edge estimator path: bit-identical
# ---------------------------------------------------------------------------

def test_engine_tc_bit_identical_to_card_fn_path(g, sk):
    fn = make_pair_cardinality_fn(g, sk)
    legacy = float(jnp.sum(fn(g.edges)) / 3.0)
    plan = eng.EnginePlan(degree_order=False)
    assert float(triangle_count(g, sk, plan=plan)) == legacy
    # kernel path: same integer popcounts -> same estimates, same fold order
    plan_k = eng.EnginePlan(use_kernel=True, degree_order=False)
    assert float(triangle_count(g, sk, plan=plan_k)) == legacy


def test_engine_4clique_bit_identical_between_paths(g, sk):
    plain = float(four_clique_count(g, sk,
                                    plan=eng.EnginePlan(edge_chunk=256,
                                                        degree_order=False)))
    kern = float(four_clique_count(g, sk,
                                   plan=eng.EnginePlan(edge_chunk=256,
                                                       use_kernel=True,
                                                       degree_order=False)))
    assert plain == kern


def test_engine_exact_tc_matches_oracle(g):
    from repro.core.exact import exact_triangle_count
    got = float(triangle_count(g, plan=eng.EnginePlan(edge_chunk=64)))
    assert got == float(int(exact_triangle_count(g)))


# ---------------------------------------------------------------------------
# multi-query session
# ---------------------------------------------------------------------------

def test_session_shares_one_edge_pass(g, sk):
    sess = eng.session(g, sk)
    first = sess.edge_cardinalities()
    assert sess.edge_cardinalities() is first      # cached, not recomputed
    np.testing.assert_allclose(float(sess.triangle_count()),
                               float(triangle_count(
                                   g, sk, plan=sess.plan)), rtol=1e-6)
    lcc = sess.local_clustering()
    np.testing.assert_allclose(
        np.asarray(lcc),
        np.asarray(local_clustering_coefficient(g, sk, plan=sess.plan)),
        rtol=1e-6)
    labels, num = sess.jarvis_patrick("jaccard", 0.05)
    labels2, num2 = jarvis_patrick(g, sk, "jaccard", 0.05, plan=sess.plan)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(labels2))
    assert int(num) == int(num2)


def test_session_builds_sketch_from_kind(g):
    sess = eng.session(g, "bf", storage_budget=0.33, num_hashes=2, seed=1)
    assert sess.sketch is not None and sess.sketch.kind == "bf"
    assert sess.stats()["sketch_bytes"] > 0
    assert float(sess.triangle_count()) > 0


def test_session_exact_mode(g):
    from repro.core.exact import exact_triangle_count
    sess = eng.session(g, None)
    assert float(sess.triangle_count()) == float(int(exact_triangle_count(g)))


# ---------------------------------------------------------------------------
# edge-axis sharding (single-device mesh: correctness of the seam)
# ---------------------------------------------------------------------------

def test_sharded_fold_matches_local(g, sk):
    plan = eng.EnginePlan(edge_chunk=64, shard_edges=True, degree_order=False)
    base = float(triangle_count(g, sk, plan=plan.with_(shard_edges=False)))
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    with sharding.use_rules(mesh):
        sharded = float(triangle_count(g, sk, plan=plan))
    np.testing.assert_allclose(sharded, base, rtol=1e-5)
    # without an active mesh the sharded plan falls back to the local fold
    assert float(triangle_count(g, sk, plan=plan)) == base
