"""Estimator correctness + property-based accuracy/bound tests (hypothesis).

Falls back to the deterministic replay shim in `_hypothesis_fallback` when
hypothesis is not installed, so the module always collects; CI installs the
real hypothesis via requirements-dev.txt.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # minimal environments
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import bounds as B
from repro.core import estimators as E
from repro.core import sketches as S
from repro.core.hashing import np_hash_u32
from repro.core.sketches import PAD_HASH, KMV_PAD, pack_bits


# ---------------------------------------------------------------------------
# helpers: build sketches of *arbitrary sets* (paper §IV works on any sets)
# ---------------------------------------------------------------------------

def bf_of(elems, words, b, seed=0):
    bits = np.zeros(words * 32, dtype=bool)
    for i in range(b):
        pos = np_hash_u32(np.asarray(list(elems), np.uint32),
                          (i + seed * 0x9E3779B9) & 0xFFFFFFFF) % (words * 32)
        bits[pos] = True
    return jnp.asarray(pack_bits(jnp.asarray(bits))[None])  # [1, words]


def khash_of(elems, k, universe, seed=0):
    elems = np.asarray(sorted(elems), np.uint32)
    out = np.full(k, universe, np.int32)
    for i in range(k):
        h = np_hash_u32(elems, (i + seed * 0x9E3779B9) & 0xFFFFFFFF)
        out[i] = elems[np.argmin(h)]
    return jnp.asarray(out[None])


def sets_strategy():
    return st.lists(st.integers(0, 4999), min_size=1, max_size=400,
                    unique=True)


# ---------------------------------------------------------------------------
# Bloom filters
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(sets_strategy(), st.integers(1, 3))
def test_bf_size_estimator_reasonable(xs, b):
    """Swamidass |X|_S within the Prop A.1 MSE-implied band (loose 6σ)."""
    words = 64  # 2048 bits — large enough that the bound is meaningful
    row = bf_of(xs, words, b)
    est = float(E.bf_size_swamidass(row, b)[0])
    mse = B.bf_linear_mse_bound(len(xs), words * 32, b)
    assert abs(est - len(xs)) <= 6 * np.sqrt(mse) + 3


@settings(max_examples=25, deadline=None)
@given(sets_strategy(), sets_strategy())
def test_bf_and_estimator_tracks_intersection(xs, ys):
    words, b = 64, 2
    inter = len(set(xs) & set(ys))
    rx, ry = bf_of(xs, words, b), bf_of(ys, words, b)
    est = float(E.bf_intersection_and(rx, ry, b)[0])
    # cross-collision inflation bound: E[extra ones] <= B p_x p_y
    mse = B.bf_and_mse_bound(max(inter, 1), words * 32, b)
    px = len(xs) * b / (words * 32)
    py = len(ys) * b / (words * 32)
    slack = words * 32 * px * py / b
    assert abs(est - inter) <= 6 * np.sqrt(mse) + 2 * slack + 3


def test_bf_and_exact_in_large_limit():
    """With B >> b|X| the AND estimator converges to the true value."""
    xs = list(range(100))
    ys = list(range(50, 150))
    row_x = bf_of(xs, 4096, 2)
    row_y = bf_of(ys, 4096, 2)
    est = float(E.bf_intersection_and(row_x, row_y, 2)[0])
    assert abs(est - 50) < 5


def test_bf_or_identity():
    xs = list(range(80))
    ys = list(range(40, 120))
    rx, ry = bf_of(xs, 2048, 2), bf_of(ys, 2048, 2)
    est = float(E.bf_intersection_or(rx, ry, 2, jnp.asarray([80.0]), jnp.asarray([80.0]))[0])
    assert abs(est - 40) < 6


# ---------------------------------------------------------------------------
# MinHash
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(sets_strategy(), sets_strategy())
def test_khash_within_exponential_bound(xs, ys):
    k = 128
    inter = len(set(xs) & set(ys))
    mx, my = khash_of(xs, k, 5000), khash_of(ys, k, 5000)
    est = float(E.khash_intersection(mx, my, jnp.asarray([float(len(xs))]),
                                     jnp.asarray([float(len(ys))]), 5000)[0])
    # invert Prop IV.2 at delta=1e-4: t = (|X|+|Y|)·sqrt(ln(2/δ)/(2k))
    t = (len(xs) + len(ys)) * np.sqrt(np.log(2 / 1e-4) / (2 * k))
    assert abs(est - inter) <= t + 1


def test_khash_jaccard_identical_sets():
    mx = khash_of(range(100), 32, 5000)
    assert float(E.khash_jaccard(mx, mx, 5000)[0]) == 1.0


def test_minhash_intersection_formula():
    # J/(1+J)·(|X|+|Y|) with J = i/(x+y-i)
    for i, x, y in [(10, 40, 50), (0, 5, 9), (30, 30, 30)]:
        j = i / (x + y - i)
        out = float(E.minhash_intersection(jnp.float32(j), jnp.float32(x), jnp.float32(y)))
        assert abs(out - i) < 1e-3


# ---------------------------------------------------------------------------
# KMV
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 100000), min_size=200, max_size=2000, unique=True))
def test_kmv_size_estimator(xs):
    from repro.core.hashing import hash_unit_interval
    k = 64
    h = np.asarray(hash_unit_interval(jnp.asarray(np.asarray(xs, np.uint32)), 0))
    row = jnp.asarray(np.sort(h)[:k][None])
    est = float(E.kmv_size(row)[0])
    # Prop A.7: deviation beyond 50% has tiny probability at k=64
    assert abs(est - len(xs)) < 0.5 * len(xs)


def test_kmv_partial_sketch_is_exact():
    from repro.core.hashing import hash_unit_interval
    xs = np.arange(10, dtype=np.uint32)
    h = np.sort(np.asarray(hash_unit_interval(jnp.asarray(xs), 0)))
    row = np.full(32, KMV_PAD, np.float32)
    row[:10] = h
    est = float(E.kmv_size(jnp.asarray(row[None]))[0])
    assert est == 10.0


# ---------------------------------------------------------------------------
# bounds module sanity
# ---------------------------------------------------------------------------

def test_bounds_monotone_in_t():
    for t1, t2 in [(1.0, 5.0), (2.0, 20.0)]:
        assert B.minhash_deviation_bound(100, 100, 64, t1) >= \
            B.minhash_deviation_bound(100, 100, 64, t2)
        assert B.bf_and_deviation_bound(50, 2048, 2, t1) >= \
            B.bf_and_deviation_bound(50, 2048, 2, t2)


def test_minhash_k_inversion():
    k = B.minhash_k_for_accuracy(100, 100, t=20, delta=0.01)
    assert B.minhash_deviation_bound(100, 100, k, 20) <= 0.011


def test_tc_bounds_shrink_with_k():
    deg = np.full(100, 10)
    assert B.tc_minhash_deviation_bound(deg, 256, 50.0) <= \
        B.tc_minhash_deviation_bound(deg, 16, 50.0)
    assert B.tc_minhash_deviation_bound_bounded_degree(deg, 256, 50.0) <= 1.0


# ---------------------------------------------------------------------------
# 1-Hash, KMV and the Bloom AND from a precomputed popcount, on seeded pairs
# of sets with a chosen overlap
# ---------------------------------------------------------------------------

OVERLAPS = [0.0, 0.1, 0.5, 0.9, 1.0]
UNIVERSE = 1 << 20          # the sentinel of the row builders below


def overlapping_pairs(seed, trials, size, overlap):
    """``trials`` pairs (X, Y) with |X| = |Y| = size and |X∩Y| = the
    rounded ``overlap · size``: int32[trials, size] rows sorted ascending,
    as the sketch builders read adjacency rows."""
    rng = np.random.default_rng(seed)
    inter = int(round(overlap * size))
    xs, ys = [], []
    for _ in range(trials):
        el = rng.choice(UNIVERSE, size=2 * size - inter, replace=False)
        xs.append(np.sort(el[:size]))
        ys.append(np.sort(el[size - inter:]))
    return (np.asarray(xs, np.int32), np.asarray(ys, np.int32), inter)


@functools.partial(jax.jit, static_argnames=("k", "variant"))
def _onehash_estimate(xs, ys, k, variant):
    """|X∩Y| from 1-Hash rows of the sorted sets ``xs`` and ``ys``."""
    mx = S.onehash_rows(xs, UNIVERSE, k)
    my = S.onehash_rows(ys, UNIVERSE, k)
    sizes = jnp.full(xs.shape[0], xs.shape[1], jnp.int32)
    return E.onehash_intersection(mx, my, S.onehash_values(mx, UNIVERSE),
                                  S.onehash_values(my, UNIVERSE), sizes,
                                  sizes, UNIVERSE, variant=variant)


@pytest.mark.parametrize("overlap", OVERLAPS)
@pytest.mark.parametrize("variant", ["naive", "union"])
def test_onehash_intersection_within_prop_iv3(variant, overlap):
    """Both 1-Hash variants stay inside Prop IV.3's tail, the bound the
    serving path's 1-Hash answers are sized by: no pair of 32 deviates by
    t with P(dev ≥ t) ≤ 1e-4 each. Disjoint and identical sets are exact."""
    size, k, trials = 400, 128, 32
    xs, ys, inter = overlapping_pairs(11, trials, size, overlap)
    est = np.asarray(_onehash_estimate(jnp.asarray(xs), jnp.asarray(ys),
                                       k=k, variant=variant))
    t = (2 * size) * np.sqrt(np.log(2 / 1e-4) / (2 * k))
    assert B.minhash_deviation_bound(size, size, k, t) <= 1e-4 + 1e-12
    assert np.max(np.abs(est - inter)) <= t
    if overlap in (0.0, 1.0):
        np.testing.assert_allclose(est, inter, rtol=1e-6)


def _kmv_union_reference(xs, ys, k):
    """KMV rows of X∪Y built directly by the program's row builder."""
    union = [np.union1d(x, y) for x, y in zip(xs, ys)]
    rows = np.full((len(union), xs.shape[1] + ys.shape[1]), UNIVERSE, np.int32)
    for i, u in enumerate(union):
        rows[i, :u.size] = u
    return S.kmv_rows(jnp.asarray(rows), UNIVERSE, k), np.array(
        [u.size for u in union])


@pytest.mark.parametrize("overlap", OVERLAPS)
def test_kmv_union_size_is_the_union_sketch(overlap):
    """``kmv_union_size`` of two KMV rows is ``kmv_size`` of the KMV row of
    X∪Y, bit for bit (the k smallest of the merged values, deduplicated),
    and within four relative standard errors 1/sqrt(k-2) of |X∪Y|."""
    size, k, trials = 600, 128, 16
    xs, ys, _ = overlapping_pairs(23, trials, size, overlap)
    kx = S.kmv_rows(jnp.asarray(xs), UNIVERSE, k)
    ky = S.kmv_rows(jnp.asarray(ys), UNIVERSE, k)
    ref_rows, union_sizes = _kmv_union_reference(xs, ys, k)
    got = np.asarray(E.kmv_union_size(kx, ky))
    np.testing.assert_array_equal(got, np.asarray(E.kmv_size(ref_rows)))
    assert np.all(np.abs(got - union_sizes) <= 4 * union_sizes / np.sqrt(k - 2))


@pytest.mark.parametrize("overlap", OVERLAPS)
def test_kmv_intersection_is_eq41(overlap):
    """``kmv_intersection`` is Eq. 41 with exact degrees, |X| + |Y| minus
    the union estimate (clamped at 0), so its error is the union
    estimate's: within four relative standard errors of |X∪Y|."""
    size, k, trials = 600, 128, 16
    xs, ys, inter = overlapping_pairs(37, trials, size, overlap)
    kx = S.kmv_rows(jnp.asarray(xs), UNIVERSE, k)
    ky = S.kmv_rows(jnp.asarray(ys), UNIVERSE, k)
    sizes = jnp.full(trials, size, jnp.int32)
    got = np.asarray(E.kmv_intersection(kx, ky, sizes, sizes))
    ref_rows, union_sizes = _kmv_union_reference(xs, ys, k)
    union_est = np.asarray(E.kmv_size(ref_rows))
    np.testing.assert_array_equal(
        got, np.maximum(np.float32(2 * size) - union_est, np.float32(0)))
    assert np.all(np.abs(got - inter) <= 4 * union_sizes / np.sqrt(k - 2))


@pytest.mark.parametrize("words", [2, 116])
@pytest.mark.parametrize("b", [1, 2, 4])
def test_bf_and_from_ones_equals_and_estimator(b, words):
    """The engine's form, Eq. 2 on the compiled pass's popcount, equals
    ``bf_intersection_and`` on the two rows bit for bit, from empty rows to
    saturated ones (where App. C-3's ones → B−1 fix applies)."""
    rng = np.random.default_rng(b * 1000 + words)
    total_bits = words * 32
    xs, ys = [], []
    for fill in (0, 1, 3, 10, 30, 100, 300, 1000, 3000):
        for _ in range(4):
            common = rng.integers(0, 1 << 30, size=fill)
            xs.append(bf_of(np.concatenate(
                [common, rng.integers(0, 1 << 30, size=fill // 3)]), words, b)[0])
            ys.append(bf_of(np.concatenate(
                [common, rng.integers(0, 1 << 30, size=fill // 5)]), words, b)[0])
    full = jnp.full((words,), 0xFFFFFFFF, jnp.uint32)
    rx = jnp.stack(xs + [full, full])
    ry = jnp.stack(ys + [full, jnp.zeros_like(full)])
    ones = jnp.sum(jax.lax.population_count(rx & ry), axis=-1)
    got = np.asarray(E.bf_intersection_and_from_ones(ones, total_bits, b))
    want = np.asarray(E.bf_intersection_and(rx, ry, b))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
