"""Sparse-frontier PPR push: equivalence, spill, streaming, and memory.

The battery behind docs/ARCHITECTURE.md invariant 10: the capped ``[S, cap]``
sparse push must agree with the dense ``[S, n]`` oracle within the ACL bound
(in practice bit-for-bit on these graphs), sweep conductance profiles must be
bit-identical on the shared support, overflow must *spill* to the dense path
(slower, never wrong), streamed sparse answers must match a fresh static
session, and the buffers must scale with ``S/(alpha·eps)`` — never ``S·n``.
"""
import functools
import math
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, strategies as st

from repro import engine as ENG
from repro.core import graph as G, sketches as SK
from repro.core.algorithms import localcluster as LC
from repro.obs import metrics as obs_metrics
from repro.stream import BatchedQueryServer, DynamicGraph, StreamSession

ALPHA = 0.15
# explicit @settings pins override any loaded hypothesis profile, so the
# nightly raise must come from the env var directly (same contract as
# tests/test_stream.py)
N_EXAMPLES = 25 if os.environ.get("HYPOTHESIS_PROFILE") == "nightly" else 5


@pytest.fixture(scope="module")
def kron():
    return _kron()


@functools.lru_cache(maxsize=None)
def _kron():
    # plain cached builder, not a fixture: @given-wrapped properties can't
    # take fixtures under the fallback shim (zero-arg wrapper)
    return G.kronecker(8, 8, seed=1)          # n = 256


def _dense(graph, seeds, eps, **kw):
    return LC.local_cluster(graph, seeds, ALPHA, eps,
                            frontier_mode="dense", **kw)


def _sparse(graph, seeds, eps, **kw):
    return LC.local_cluster(graph, seeds, ALPHA, eps,
                            frontier_mode="sparse", **kw)


def _assert_profiles_match(res_d, res_s):
    """Dense/sparse sweep agreement: identical order on the shared prefix
    width, bit-identical conductance wherever the orders agree."""
    k = min(res_d.order.shape[1], res_s.order.shape[1])
    ord_d = np.asarray(res_d.order)[:, :k]
    ord_s = np.asarray(res_s.order)[:, :k]
    np.testing.assert_array_equal(ord_d, ord_s)
    np.testing.assert_array_equal(np.asarray(res_d.conductance)[:, :k],
                                  np.asarray(res_s.conductance)[:, :k])
    np.testing.assert_array_equal(np.asarray(res_d.support),
                                  np.asarray(res_s.support))


# ---------------------------------------------------------------------------
# sparse == dense (hypothesis-driven)
# ---------------------------------------------------------------------------

@settings(max_examples=N_EXAMPLES, deadline=None)
@given(gseed=st.integers(0, 3), sseed=st.integers(0, 6),
       eps_i=st.integers(0, 2))
def test_sparse_push_matches_dense_fuzz(gseed, sseed, eps_i):
    g = G.erdos_renyi(96, 0.06, seed=gseed)   # one shape class per example
    eps = (2e-2, 8e-3, 3e-3)[eps_i]
    rng = np.random.default_rng(sseed)
    seeds = rng.integers(0, g.n, size=4).astype(np.int32)
    p, r, it_d = LC.ppr_push(g, seeds, ALPHA, eps)
    fr = LC.ppr_push_sparse(g, seeds, ALPHA, eps)
    assert not bool(fr.overflowed)
    assert int(fr.iterations) == int(it_d)
    pd, rd = fr.densify()
    # within the ACL slack both are valid answers; in practice the sparse
    # merge reproduces the dense scatter-adds to float32 round-off
    np.testing.assert_allclose(np.asarray(pd), np.asarray(p), atol=1e-6)
    np.testing.assert_allclose(np.asarray(rd), np.asarray(r), atol=1e-6)
    # identical support sets, straight from the index buffer
    dense_sup = (np.asarray(p) > 0) | (np.asarray(r) > 0)
    sparse_sup = (np.asarray(pd) > 0) | (np.asarray(rd) > 0)
    np.testing.assert_array_equal(dense_sup, sparse_sup)


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(sseed=st.integers(0, 8))
def test_sweep_profiles_bit_identical_on_shared_support(sseed):
    kron = _kron()
    rng = np.random.default_rng(sseed)
    seeds = rng.integers(0, kron.n, size=3).astype(np.int32)
    eps = 5e-3
    res_d = _dense(kron, seeds, eps)
    res_s = _sparse(kron, seeds, eps)
    assert res_s.frontier is not None and not res_s.spilled
    assert res_d.frontier is None
    _assert_profiles_match(res_d, res_s)
    np.testing.assert_array_equal(np.asarray(res_d.best_conductance),
                                  np.asarray(res_s.best_conductance))


def test_sparse_sweep_with_sketch_is_bit_identical_to_dense_sketch(kron):
    # the sketch-gated increments read only (order, deg, adj, sketch) — the
    # prefix-OR estimator is untouched by the frontier layout
    seeds = np.array([3, 17, 101], np.int32)
    sk = SK.build(kron, "bf", storage_budget=2.0)
    res_d = _dense(kron, seeds, 5e-3, sketch=sk)
    res_s = _sparse(kron, seeds, 5e-3, sketch=sk)
    _assert_profiles_match(res_d, res_s)


def test_sparse_acl_invariant_vs_power_iteration(kron):
    eps = 2e-3
    seeds = np.array([3, 17], np.int32)
    fr = LC.ppr_push_sparse(kron, seeds, ALPHA, eps, max_iters=500)
    assert not bool(fr.overflowed)
    p, r = fr.densify()
    ref = LC.ppr_power_iteration(kron, seeds, ALPHA, iters=400)
    err = np.asarray(ref) - np.asarray(p)
    bound = eps * np.asarray(kron.deg, np.float64)[None, :] + 1e-4
    assert (err <= bound).all() and (err >= -1e-4).all()
    thresh = eps * np.maximum(np.asarray(kron.deg, np.float64), 1.0)
    assert (np.asarray(r) < thresh[None, :] + 1e-7).all()


def test_sparse_footprint_matches_dense(kron):
    seeds = np.array([3, 200], np.int32)
    res_d = _dense(kron, seeds, 5e-3)
    res_s = _sparse(kron, seeds, 5e-3)
    for s in range(len(seeds)):
        fp_d, fp_s = res_d.footprint(s), res_s.footprint(s)
        np.testing.assert_array_equal(fp_d, fp_s)
        assert (np.diff(fp_s) > 0).all()          # sorted, duplicate-free


# ---------------------------------------------------------------------------
# overflow spill: perf event, never a correctness event
# ---------------------------------------------------------------------------

def test_overflow_spills_to_dense(kron):
    seeds = np.array([3, 17, 101], np.int32)
    fr = LC.ppr_push_sparse(kron, seeds, ALPHA, 1e-3, frontier_cap=4)
    assert bool(fr.overflowed)

    spills_before = obs_metrics.REGISTRY.counter("ppr.spill").value
    res_s = _sparse(kron, seeds, 1e-3, frontier_cap=4)
    assert res_s.spilled and res_s.frontier is None
    assert res_s.ppr is not None                  # dense fallback ran
    assert obs_metrics.REGISTRY.counter("ppr.spill").value \
        == spills_before + 1
    # the spilled answer IS the dense answer, bit for bit
    res_d = _dense(kron, seeds, 1e-3)
    np.testing.assert_array_equal(np.asarray(res_d.order),
                                  np.asarray(res_s.order))
    np.testing.assert_array_equal(np.asarray(res_d.conductance),
                                  np.asarray(res_s.conductance))
    for s in range(len(seeds)):
        np.testing.assert_array_equal(res_d.footprint(s), res_s.footprint(s))


def test_auto_mode_selects_by_cap_vs_n(kron):
    # tight eps on a small graph: the ACL cap rivals n, auto must go dense
    assert LC.resolve_frontier_mode(
        ENG.EnginePlan(), kron.n, kron.d_max, ALPHA, 1e-4) == "dense"
    # loose eps on a big n: cap is far below n, auto must go sparse
    assert LC.resolve_frontier_mode(
        ENG.EnginePlan(), 1 << 20, 64, ALPHA, 3e-2) == "sparse"
    # ... unless the rows each slot gathers make a sparse round as large as
    # a dense one (Graph500 scale 16: n = 65,536, d_max = 9,729, cap 8,192)
    assert LC.resolve_frontier_mode(
        ENG.EnginePlan(), 1 << 16, 9729, ALPHA, 1e-3) == "dense"
    with pytest.raises(ValueError):
        LC.resolve_frontier_mode(
            ENG.EnginePlan(frontier_mode="bogus"), kron.n, kron.d_max,
            ALPHA, 1e-2)
    res = LC.local_cluster(kron, np.array([3], np.int32), ALPHA, 1e-4)
    assert res.frontier is None and not res.spilled   # auto stayed dense


# ---------------------------------------------------------------------------
# streaming: sparse answers over deltas == fresh static session
# ---------------------------------------------------------------------------

def test_stream_sparse_localcluster_matches_static(kron):
    rng = np.random.default_rng(7)
    edges = np.asarray(kron.edges)
    keep = rng.permutation(edges.shape[0])
    initial, arriving = edges[keep[:-200]], edges[keep[-200:]]
    sess = StreamSession(DynamicGraph.from_edges(kron.n, initial), kind="bf",
                         storage_budget=1.0)
    seeds = np.array([3, 17, 101], np.int32)
    kw = dict(frontier_mode="sparse", frontier_cap=256)
    sess.apply_delta(inserts=arriving[:120])
    mid = sess.local_cluster(seeds, ALPHA, 5e-3, **kw)     # interleaved query
    assert mid.frontier is not None
    sess.apply_delta(inserts=arriving[120:],
                     deletes=initial[rng.choice(initial.shape[0], 15,
                                                replace=False)])
    res_stream = sess.local_cluster(seeds, ALPHA, 5e-3, **kw)
    assert res_stream.frontier is not None and not res_stream.spilled

    gs = G.from_edge_array(sess.dyn.n, sess.dyn.edge_array())
    mt = sess.maintainer
    sk = SK.build(gs, mt.kind, words=mt.words, num_hashes=mt.num_hashes,
                  seed=mt.seed)
    res_static = ENG.session(gs, sk, plan=sess.session.plan).local_cluster(
        seeds, ALPHA, 5e-3, **kw)
    np.testing.assert_array_equal(np.asarray(res_stream.order),
                                  np.asarray(res_static.order))
    np.testing.assert_array_equal(np.asarray(res_stream.conductance),
                                  np.asarray(res_static.conductance))
    np.testing.assert_array_equal(np.asarray(res_stream.best_conductance),
                                  np.asarray(res_static.best_conductance))
    np.testing.assert_array_equal(np.asarray(res_stream.frontier.idx),
                                  np.asarray(res_static.frontier.idx))


def test_server_serves_sparse_localcluster(kron):
    sess = StreamSession(DynamicGraph.from_graph(kron), kind="bf",
                         storage_budget=1.0, frontier_mode="sparse",
                         frontier_cap=256)
    srv = BatchedQueryServer(sess)
    rids = [srv.submit_local_cluster(s, eps=5e-3) for s in (3, 17, 101)]
    out = srv.flush()
    direct = sess.local_cluster(np.array([3, 17, 101], np.int32), ALPHA,
                                5e-3)
    for i, rid in enumerate(rids):
        val = out[rid].value
        assert val["size"] == int(direct.best_size[i])
        np.testing.assert_array_equal(val["members"], direct.members(i))


# ---------------------------------------------------------------------------
# memory: O(S/(alpha·eps)) buffers, never O(S·n)
# ---------------------------------------------------------------------------

def test_memory_scales_with_support_bound_not_n():
    eps = 5e-2
    bound = math.ceil(1.0 / (ALPHA * eps))               # ACL support bound
    seeds = np.array([1, 2, 3], np.int32)
    caps, small_n = [], None
    for scale in (8, 10):                                # n = 256, 1024
        g = G.kronecker(scale, 6, seed=2)
        fr = LC.ppr_push_sparse(g, seeds, ALPHA, eps)
        assert not bool(fr.overflowed)
        caps.append(fr.cap)
        # pow2 bucketing costs at most 2x over the analytic bound
        assert fr.cap <= 2 * bound
        # peak residual-buffer bytes: exactly S·cap floats, independent of n
        assert fr.r.nbytes == seeds.size * fr.cap * 4
        assert fr.p.nbytes == seeds.size * fr.cap * 4
        small_n = small_n or g.n
    assert caps[0] == caps[1]                 # grew n 4x, buffers unchanged
    # and the dense residual it replaces is strictly O(S·n)
    g = G.kronecker(10, 6, seed=2)
    _, r_dense, _ = LC.ppr_push(g, seeds, ALPHA, eps)
    assert r_dense.nbytes == seeds.size * g.n * 4
    assert r_dense.nbytes >= 4 * fr.r.nbytes


def test_frontier_cap_for_clamps_and_buckets():
    assert LC.frontier_cap_for(0.15, 5e-2, n=1 << 20) == 256
    assert LC.frontier_cap_for(0.15, 5e-2, n=64) == 64       # pow2(n) clamp
    assert LC.frontier_cap_for(0.15, 1e-2, n=1 << 20, override=100) == 128
    assert LC.frontier_cap_for(0.5, 0.5, n=1 << 20) == 4     # lo clamp ≥ 2
