"""Set-expression compiler (SISA layer): golden bit-identity against the
legacy hand-rolled kernels, compile-cache behavior, deprecation shims, and
the cliques5 workload end-to-end (engine, launch seam, serving tier).

This file is also the ``-W error::DeprecationWarning`` CI gate: the engine
paths exercised here must not touch the deprecated ``bf_intersect`` names.
"""
import itertools
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro import engine as eng
from repro.core import (bounds, five_clique_count, four_clique_count,
                        graph as G, sketches as S, triangle_count)
from repro.core.algorithms import localcluster as LC
from repro.engine import setexpr
from repro.kernels import bf_intersect as legacy
from repro.kernels import ops, ref
from repro.stream import BatchedQueryServer, ErrorBudgetPolicy, stream_session


def _np_popcount(rows: np.ndarray) -> np.ndarray:
    """Reference popcount over the trailing word axis."""
    return np.unpackbits(
        np.ascontiguousarray(rows).view(np.uint8),
        axis=-1).sum(axis=-1).astype(np.int32)


def _pad_rows(x, mult, fill=0):
    pad = (-x.shape[0]) % mult
    return np.concatenate(
        [x, np.full((pad, *x.shape[1:]), fill, x.dtype)], axis=0)


def _pad_cols(x, mult):
    pad = (-x.shape[1]) % mult
    return np.concatenate(
        [x, np.zeros((x.shape[0], pad), x.dtype)], axis=1)


@pytest.fixture(scope="module")
def bloom(rng):
    return jnp.asarray(rng.integers(0, 2**32, size=(60, 10), dtype=np.uint32))


# ---------------------------------------------------------------------------
# golden bit-identity: compiled expressions vs the legacy raw kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,block_e,block_w", [(40, 8, 512), (129, 8, 512),
                                               (3, 1, 512), (21, 8, 4),
                                               (64, 64, 512)])
def test_compiled_and2_gather_matches_legacy(bloom, rng, t, block_e, block_w):
    """Gather-form 2-way AND == the private fixed-arity gather form, bit for
    bit, on ragged tuple counts and ragged word axes."""
    n, w = bloom.shape
    edges = rng.integers(0, n, size=(t, 2), dtype=np.int32)
    u, v = setexpr.rows(2)
    ce = setexpr.compile_expr(u & v, block_e=block_e, block_w=block_w)
    got = np.asarray(ce.ones(bloom, jnp.asarray(edges)))
    # drive the private legacy kernel with the pre-PR padding contract
    be = min(block_e, t)
    bw = min(block_w, w)
    want = np.asarray(legacy._edge_impl(
        jnp.asarray(_pad_cols(np.asarray(bloom), bw)),
        jnp.asarray(_pad_rows(edges, be)),
        block_e=be, block_w=bw, interpret=True))[:t]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, np.asarray(ref.bf_edge_intersect(bloom, jnp.asarray(edges))))
    # jnp lowering of the same expression: identical integers
    ce_j = setexpr.compile_expr(u & v, use_kernel=False)
    np.testing.assert_array_equal(
        np.asarray(ce_j.ones(bloom, jnp.asarray(edges))), got)


def test_compiled_and3_gather_matches_legacy(bloom, rng):
    """Gather-form 3-way AND == the pre-PR 3-slab kernel, bit for bit."""
    n, w = bloom.shape
    triples = rng.integers(0, n, size=(37, 3), dtype=np.int32)
    ce = setexpr.compile_expr(setexpr.and_all(*setexpr.rows(3)))
    got = np.asarray(ce.ones(bloom, jnp.asarray(triples)))
    be = min(8, 37)
    want = np.asarray(legacy._edge3_impl(
        bloom, jnp.asarray(_pad_rows(triples, be)),
        block_e=be, block_w=w, interpret=True))[:37]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, np.asarray(ref.bf_edge_intersect3(bloom, jnp.asarray(triples))))


@pytest.mark.parametrize("e,w", [(1, 2), (7, 2), (64, 16), (257, 30)])
def test_compiled_and2_dense_matches_legacy(rng, e, w):
    """Dense-form 2-way AND (the sweep-gating shape) == the pre-PR pairs
    kernel on ragged row counts and odd word widths."""
    a = rng.integers(0, 2**32, size=(e, w), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(e, w), dtype=np.uint32)
    u, v = setexpr.rows(2)
    ce = setexpr.compile_expr(u & v, block_e=256, block_w=512)
    got = np.asarray(ce.ones_rows(jnp.asarray(a), jnp.asarray(b)))
    be = min(256, e)
    a2 = _pad_cols(_pad_rows(a, be), 2)
    want = np.asarray(legacy._pairs_impl(
        jnp.asarray(a2), jnp.asarray(_pad_cols(_pad_rows(b, be), 2)),
        block_e=be, block_w=min(512, a2.shape[1]), interpret=True))[:e]
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, np.asarray(ref.bf_intersect_pairs(jnp.asarray(a),
                                               jnp.asarray(b))))
    # jnp lowering agrees too
    ce_j = setexpr.compile_expr(u & v, use_kernel=False)
    np.testing.assert_array_equal(
        np.asarray(ce_j.ones_rows(jnp.asarray(a), jnp.asarray(b))), got)


def test_compiled_or_andnot_nested_match_reference(bloom, rng):
    """OR / ANDNOT / nested trees: kernel and jnp lowerings both equal the
    numpy popcount of the same bitwise formula."""
    n = bloom.shape[0]
    tuples = rng.integers(0, n, size=(33, 3), dtype=np.int32)
    data = np.asarray(bloom)
    ra, rb, rc = (data[tuples[:, i]] for i in range(3))
    u, v, t3 = setexpr.rows(3)
    cases = [
        (u | v, ra | rb),
        (u - v, ra & ~rb),
        ((u & v) | t3, (ra & rb) | rc),
        ((u | v) - t3, (ra | rb) & ~rc),
        (setexpr.or_all(u, v, t3), ra | rb | rc),
    ]
    for expr, rows_np in cases:
        want = _np_popcount(rows_np)
        for use_kernel in (True, False):
            ce = setexpr.compile_expr(expr, use_kernel=use_kernel)
            got = np.asarray(ce.ones(bloom, jnp.asarray(tuples)))
            np.testing.assert_array_equal(got, want, err_msg=repr(expr))


def test_four_way_and_matches_reference(bloom, rng):
    """The cliques5 workhorse (4-way AND) needs no new kernel."""
    n = bloom.shape[0]
    quads = rng.integers(0, n, size=(19, 4), dtype=np.int32)
    data = np.asarray(bloom)
    want = _np_popcount(data[quads[:, 0]] & data[quads[:, 1]]
                        & data[quads[:, 2]] & data[quads[:, 3]])
    ce = setexpr.compile_expr(setexpr.and_all(*setexpr.rows(4)))
    np.testing.assert_array_equal(
        np.asarray(ce.ones(bloom, jnp.asarray(quads))), want)
    plan = eng.EnginePlan(use_kernel=True)
    sk = S.SketchSet(data=bloom, kind="bf", num_hashes=2, k=0, seed=0, n=n)
    np.testing.assert_array_equal(
        np.asarray(eng.tuple_cardinality_ones(sk, jnp.asarray(quads), plan)),
        want)


def test_compiled_expr_edge_cases(bloom):
    """Empty inputs, narrow tuples, wrong dense arity, leafless trees."""
    u, v = setexpr.rows(2)
    ce = setexpr.compile_expr(u & v)
    out = ce.ones(bloom, jnp.zeros((0, 2), jnp.int32))
    assert out.shape == (0,) and out.dtype == jnp.int32
    assert ce.ones_rows(jnp.zeros((0, 4), jnp.uint32),
                        jnp.zeros((0, 4), jnp.uint32)).shape == (0,)
    with pytest.raises(ValueError):
        ce.ones(bloom, jnp.zeros((3, 1), jnp.int32))     # needs column 1
    with pytest.raises(ValueError):
        ce.ones_rows(jnp.zeros((3, 4), jnp.uint32))      # needs 2 operands


def test_sweep_cut_kernel_vs_jnp_bit_identical():
    """The rerouted sweep gating (dense compiled AND) gives bit-identical
    conductance profiles on both lowerings."""
    g = G.kronecker(7, 6, seed=2)
    sk = S.build(g, "bf", 0.5, num_hashes=2, seed=1)
    seeds = np.array([3, 17, 40], np.int32)
    res_j = LC.local_cluster(g, seeds, 0.15, 1e-3, sk,
                             plan=eng.EnginePlan(use_kernel=False))
    res_k = LC.local_cluster(g, seeds, 0.15, 1e-3, sk,
                             plan=eng.EnginePlan(use_kernel=True))
    np.testing.assert_array_equal(np.asarray(res_j.conductance),
                                  np.asarray(res_k.conductance))
    np.testing.assert_array_equal(np.asarray(res_j.order),
                                  np.asarray(res_k.order))


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_keyed_by_structure():
    """Same expression structure + config -> the same compiled object;
    different structure or block shape -> a fresh one."""
    setexpr.cache_clear()
    u, v = setexpr.rows(2)
    c1 = setexpr.compile_expr(u & v)
    c2 = setexpr.compile_expr(setexpr.rows(2)[0] & setexpr.rows(2)[1])
    assert c1 is c2
    assert setexpr.cache_info() == {"size": 1, "hits": 1}
    c3 = setexpr.compile_expr(u & v, block_e=16)
    c4 = setexpr.compile_expr(u | v)
    assert c3 is not c1 and c4 is not c1
    assert setexpr.cache_info()["size"] == 3


def test_expression_structure_and_flattening():
    """Operator sugar flattens chains; keys are canonical nested tuples."""
    u, v, w, x = setexpr.rows(4)
    assert (u & v & w & x).key() == ("and", ("row", 0), ("row", 1),
                                    ("row", 2), ("row", 3))
    assert setexpr.and_all(u & v, w & x).key() == (u & v & w & x).key()
    assert (u | (v | w)).key() == ("or", ("row", 0), ("row", 1), ("row", 2))
    assert ((u & v) - w).key() == ("andnot", ("and", ("row", 0), ("row", 1)),
                                  ("row", 2))
    assert setexpr.expr_slots((x & v) - u) == (0, 1, 3)


# ---------------------------------------------------------------------------
# deprecation shims + clean engine paths
# ---------------------------------------------------------------------------

def test_legacy_kernel_names_warn(bloom, rng):
    """The old public names in bf_intersect still work — and warn."""
    n, w = bloom.shape
    edges = jnp.asarray(rng.integers(0, n, size=(8, 2), dtype=np.int32))
    a = jnp.asarray(rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32))
    with pytest.warns(DeprecationWarning, match="bf_edge_intersect "):
        out = legacy.bf_edge_intersect(bloom, edges, block_e=8, block_w=w,
                                       interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.bf_edge_intersect(bloom, edges)))
    with pytest.warns(DeprecationWarning):
        legacy.bf_intersect_pairs(a, a, block_e=8, block_w=4, interpret=True)
    with pytest.warns(DeprecationWarning):
        legacy.bf_intersect3_pairs(a, a, a, block_e=8, block_w=4,
                                   interpret=True)
    triples = jnp.asarray(rng.integers(0, n, size=(8, 3), dtype=np.int32))
    with pytest.warns(DeprecationWarning):
        legacy.bf_edge_intersect3(bloom, triples, block_e=8, block_w=w,
                                  interpret=True)


def test_engine_paths_free_of_deprecated_entrypoints():
    """Kernel-path TC, 4/5-cliques and sweep cuts must not route through
    the deprecated names (this is what the -W error CI step enforces)."""
    g = G.erdos_renyi(60, 0.15, seed=4)
    sk = S.build(g, "bf", 0.5, num_hashes=2, seed=1)
    plan = eng.EnginePlan(use_kernel=True, degree_order=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        float(triangle_count(g, sk, plan=plan))
        float(four_clique_count(g, sk, plan=plan.with_(edge_chunk=64)))
        float(five_clique_count(g, sk, plan=plan.with_(edge_chunk=32)))
        LC.local_cluster(g, np.array([3], np.int32), 0.15, 1e-2, sk,
                         plan=plan)


# ---------------------------------------------------------------------------
# cliques5: exact enumeration, estimator accuracy, path bit-identity
# ---------------------------------------------------------------------------

def _brute_five_cliques(g) -> int:
    """Literal itertools enumeration of 5-cliques."""
    nbrs = {}
    for a, b in np.asarray(g.edges):
        nbrs.setdefault(int(a), set()).add(int(b))
        nbrs.setdefault(int(b), set()).add(int(a))
    count = 0
    for clique in itertools.combinations(sorted(nbrs), 5):
        if all(q in nbrs[p] for p, q in itertools.combinations(clique, 2)):
            count += 1
    return count


@pytest.mark.parametrize("make", [
    lambda: G.erdos_renyi(18, 0.5, seed=3),
    lambda: G.erdos_renyi(25, 0.4, seed=11),
    lambda: G.kronecker(5, 6, seed=2),
])
def test_cliques5_exact_matches_bruteforce(make):
    g = make()
    want = float(_brute_five_cliques(g))
    assert float(five_clique_count(g)) == want
    # chunk-size invariance of the fold
    assert float(five_clique_count(
        g, plan=eng.EnginePlan(edge_chunk=7))) == want


def test_cliques5_bf_estimate_and_path_identity():
    g = G.erdos_renyi(18, 0.5, seed=3)
    want = _brute_five_cliques(g)
    sk = S.build(g, "bf", 4.0, num_hashes=2, seed=1)
    got_k = float(five_clique_count(
        g, sk, plan=eng.EnginePlan(edge_chunk=64, use_kernel=True)))
    got_j = float(five_clique_count(
        g, sk, plan=eng.EnginePlan(edge_chunk=64, use_kernel=False)))
    assert got_k == got_j                     # same compiled expression
    assert abs(got_k - want) / max(want, 1) < 0.35
    # the k-way bound degrades gracefully with k (same Prop IV.1 form)
    assert (bounds.bf_kway_and_mse_bound(5.0, 1024, 2, k=4)
            == bounds.bf_and_mse_bound(5.0, 1024, 2))
    with pytest.raises(ValueError):
        bounds.bf_kway_and_mse_bound(5.0, 1024, 2, k=1)


def test_cliques5_rejects_unsupported_sketch():
    g = G.erdos_renyi(20, 0.3, seed=1)
    sk = S.build(g, "kh", 0.5, seed=1)
    with pytest.raises(ValueError, match="sketch kind"):
        five_clique_count(g, sk)


def test_session_five_clique_count():
    g = G.erdos_renyi(18, 0.5, seed=3)
    sess = eng.session(g, None)
    assert float(sess.five_clique_count()) == float(_brute_five_cliques(g))


# ---------------------------------------------------------------------------
# serving tier: the new query kind caches and invalidates like tc
# ---------------------------------------------------------------------------

def test_server_clique_count_cached_and_invalidated():
    g = G.erdos_renyi(36, 0.25, seed=7)
    st = stream_session(g, "bf", words=4, num_hashes=2, seed=3,
                        policy=ErrorBudgetPolicy(0.0))
    srv = BatchedQueryServer(st, min_batch=8)
    r4 = srv.submit_clique_count(4)
    r5 = srv.submit_clique_count(5)
    out = srv.flush()
    assert out[r4].value == float(st.four_clique_count())
    assert out[r5].value == float(st.five_clique_count())
    # resubmission with no intervening delta is a cache hit, same object
    hits0 = srv.cache.hits
    h5 = srv.submit_clique_count(5)
    assert srv.flush()[h5].value == out[r5].value
    assert srv.cache.hits > hits0
    # whole-graph footprint: any delta invalidates the cached count
    st.apply_delta(np.array([[1, 3]]), np.zeros((0, 2), np.int64))
    r5b = srv.submit_clique_count(5)
    assert srv.flush()[r5b].value == float(st.five_clique_count())
    with pytest.raises(ValueError):
        srv.submit_clique_count(3)


# ---------------------------------------------------------------------------
# public API surface
# ---------------------------------------------------------------------------

def test_engine_api_facade_exports():
    """launch/stream import from repro.engine.api — pin the surface."""
    from repro.engine import api
    for name in ("EnginePlan", "Footprint", "MiningSession", "SetExpr",
                 "compile_expr", "edge_cardinalities", "map_edges",
                 "pair_cardinality_fn", "pow2_bucket", "resolve_plan",
                 "rows", "session", "tuple_cardinality_ones",
                 "wedge_quad_ones"):
        assert hasattr(api, name), name
    for name in ("_sharded_fold", "engine"):
        assert name not in api.__all__


def test_kernel_knobs_are_keyword_only(bloom):
    """Tuning knobs (block_e/block_w/interpret) reject positional use."""
    edges = jnp.zeros((4, 2), jnp.int32)
    with pytest.raises(TypeError):
        ops.bf_edge_intersect(bloom, edges, 8)
    with pytest.raises(TypeError):
        setexpr.compile_expr(setexpr.rows(2)[0] & setexpr.rows(2)[1], 8)
