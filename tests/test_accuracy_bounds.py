"""The paper's accuracy claims as executable regressions.

Two layers, both on seeded Erdős–Rényi + Kronecker graphs so every number
is deterministic:

* **Concentration intervals** (Theorem VII.1 via ``core.bounds``): the
  BF/KMV/kH triangle-count estimate must land inside the smallest deviation
  ``t`` whose tail probability is ≤ the configured confidence. The interval
  is inverted analytically here and cross-checked against the bounds module
  itself, so a regression in either the estimators *or* the bounds breaks
  the suite.
* **The >90%-accuracy headline claim** (paper §IX): with modestly sized
  sketches on power-law (Kronecker) graphs, all three TC estimators must
  stay within 10% relative error.

The large configurations are ``slow`` (nightly); one small configuration of
each layer stays in the fast gate as a smoke.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as eng
from repro.core import bounds, estimators as E, graph as G, sketches as S
from repro.core.hashing import np_hash_u32

CONF = 0.1      # 90%-confidence intervals
SKETCH_SEED = 0


@functools.lru_cache(maxsize=None)
def graph_and_exact(name):
    gs = {
        "er200": lambda: G.erdos_renyi(200, 0.06, seed=11),
        "kron7": lambda: G.kronecker(7, 6, seed=3),
        "er800": lambda: G.erdos_renyi(800, 0.02, seed=7),
        "kron9": lambda: G.kronecker(9, 8, seed=5),
    }[name]()
    return gs, float(eng.session(gs, None).triangle_count())


def tc_interval(gs, sk, conf=CONF):
    """Smallest deviation t with Thm VII.1 tail probability ≤ conf."""
    deg = np.asarray(gs.deg)
    if sk.kind == "bf":
        # invert tc_bf_deviation_bound: 2 m² mse / (9 t²) ≤ conf, with the
        # MSE taken from the bounds module itself (single formula home)
        mse = bounds.bf_and_mse_bound(float(deg.max()), sk.total_bits,
                                      sk.num_hashes)
        return float(np.sqrt(2.0 * gs.m**2 * max(mse, 0.0) / (9.0 * conf)))
    # invert tc_minhash_deviation_bound: 2 exp(−18kt²/s2²) ≤ conf
    s2 = float(np.sum(deg.astype(np.float64) ** 2))
    return float(s2 * np.sqrt(np.log(2.0 / conf) / (18.0 * sk.k)))


def assert_within_interval(name, kind, storage_budget=0.5):
    gs, exact = graph_and_exact(name)
    sk = S.build(gs, kind, storage_budget=storage_budget, num_hashes=2,
                 seed=SKETCH_SEED)
    est = float(eng.session(gs, sk).triangle_count())
    t = tc_interval(gs, sk)
    # the inverted interval must agree with the bounds module itself
    if kind == "bf":
        p = bounds.tc_bf_deviation_bound(gs.m, int(np.asarray(gs.deg).max()),
                                         sk.total_bits, sk.num_hashes, t)
    else:
        p = bounds.tc_minhash_deviation_bound(np.asarray(gs.deg), sk.k, t)
    assert p <= CONF + 1e-9, (name, kind, p)
    assert abs(est - exact) <= t, \
        f"{name}/{kind}: |{est:.1f} - {exact:.1f}| > t={t:.1f}"


# ---------------------------------------------------------------------------
# layer 1: estimates land inside the Thm VII.1 concentration intervals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["bf", "kmv", "kh"])
def test_tc_within_interval_smoke(kind):
    assert_within_interval("kron7", kind)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["er200", "er800", "kron9"])
@pytest.mark.parametrize("kind", ["bf", "kmv", "kh"])
def test_tc_within_interval(name, kind):
    assert_within_interval(name, kind)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["bf", "kmv", "kh"])
def test_tc_interval_shrinks_with_budget(kind):
    """More storage ⇒ a strictly tighter concentration interval."""
    gs, _ = graph_and_exact("kron7")
    small = S.build(gs, kind, storage_budget=0.25, num_hashes=2,
                    seed=SKETCH_SEED)
    large = S.build(gs, kind, storage_budget=1.0, num_hashes=2,
                    seed=SKETCH_SEED)
    assert tc_interval(gs, large) < tc_interval(gs, small)


# ---------------------------------------------------------------------------
# layer 2: the >90%-accuracy headline claim, executable
# ---------------------------------------------------------------------------

NINETY = [  # (graph, kind, explicit sketch size) — all must stay ≤ 10% off
    ("kron7", "bf", dict(words=128)),
    ("kron7", "kmv", dict(k=128)),
    ("kron7", "kh", dict(k=128)),
]
NINETY_SLOW = [
    ("kron9", "bf", dict(words=256)),
    ("kron9", "kmv", dict(k=256)),
    ("kron9", "kh", dict(k=256)),
    ("er200", "bf", dict(words=256)),
    ("er200", "kh", dict(k=128)),
]


def assert_ninety(name, kind, kw):
    gs, exact = graph_and_exact(name)
    sk = S.build(gs, kind, num_hashes=2, seed=SKETCH_SEED, **kw)
    est = float(eng.session(gs, sk).triangle_count())
    rel = abs(est - exact) / max(exact, 1.0)
    assert rel <= 0.10, f"{name}/{kind}{kw}: relative error {rel:.3f} > 10%"


@pytest.mark.parametrize("name,kind,kw", NINETY)
def test_tc_ninety_percent_accuracy_smoke(name, kind, kw):
    assert_ninety(name, kind, kw)


@pytest.mark.slow
@pytest.mark.parametrize("name,kind,kw", NINETY_SLOW)
def test_tc_ninety_percent_accuracy(name, kind, kw):
    assert_ninety(name, kind, kw)


# ---------------------------------------------------------------------------
# layer 3: the error scales the streaming error budget reads, against
# seeded trials of the estimators they stand for
# ---------------------------------------------------------------------------

TRIALS = 300
_GOLDEN = 0x9E3779B9


def _trial_pairs(seed, inter, private):
    """TRIALS pairs of sets sharing ``inter`` elements, each with
    ``private`` more of its own: uint32[TRIALS, inter + private] each."""
    rng = np.random.default_rng(seed)
    el = np.stack([rng.choice(1 << 30, size=inter + 2 * private, replace=False)
                   for _ in range(TRIALS)]).astype(np.uint32)
    x = el[:, :inter + private]
    y = np.concatenate([el[:, :inter], el[:, inter + private:]], axis=1)
    return x, y


def _bloom_rows(sets, words, b):
    """Bloom rows of each row of ``sets``, hashed as ``core.sketches``
    hashes (function i seeded with i, sketch seed 0)."""
    trials, size = sets.shape
    out = np.zeros((trials, words), np.uint32)
    rows = np.repeat(np.arange(trials), size)
    for i in range(b):
        pos = np_hash_u32(sets.ravel(), i) % np.uint32(words * 32)
        np.bitwise_or.at(out, (rows, pos >> 5),
                         np.uint32(1) << (pos & 31).astype(np.uint32))
    return out


@pytest.mark.parametrize("inter,words,b", [(100, 32, 2), (400, 116, 2),
                                           (50, 4, 2)])
def test_bf_and_rmse_matches_bloom_trials(inter, words, b):
    """``bf_and_rmse`` is Prop IV.1's RMSE of |X∩Y|_AND: over 300 seeded
    pairs it lies within 20 % of the empirical RMSE (the sampling error of
    an RMSE over 300 trials is about 4 %). Fill c·b/B runs from 0.2 to
    0.78. Each set holds a tenth of |X∩Y| of its own; cross-collisions of
    those private bits bias the AND upwards, which the formula leaves out."""
    x, y = _trial_pairs(inter * 7 + words, inter, inter // 10)
    est = np.asarray(E.bf_intersection_and(
        jnp.asarray(_bloom_rows(x, words, b)),
        jnp.asarray(_bloom_rows(y, words, b)), b))
    empirical = float(np.sqrt(np.mean((est - inter) ** 2)))
    scale = float(bounds.bf_and_rmse(inter, words * 32, b))
    assert 0.8 <= empirical / scale <= 1.25, (empirical, scale)


@pytest.mark.parametrize("size", [128, 512])
@pytest.mark.parametrize("kind", ["kh", "1h"])
def test_minhash_error_scale_exceeded_at_most_delta(kind, size):
    """``minhash_error_scale(size, k, δ)`` inverts Prop IV.2 (and IV.3, of
    the same form): the |X∩Y| estimate of two sets of ``size`` elements
    each, half shared, misses by more than it in at most a share δ of
    trials. Tolerance: three binomial standard errors over 300 trials."""
    k, delta, n = 64, 0.05, 1 << 30
    inter = size // 2
    x, y = _trial_pairs(size + len(kind), inter, size - inter)
    rx = jnp.asarray(np.sort(x, axis=1).astype(np.int32))
    ry = jnp.asarray(np.sort(y, axis=1).astype(np.int32))
    sizes = jnp.full(TRIALS, size, jnp.int32)

    @jax.jit
    def estimate(rx, ry):
        if kind == "kh":
            return E.khash_intersection(S.khash_rows(rx, n, k),
                                        S.khash_rows(ry, n, k), sizes, sizes, n)
        mx, my = S.onehash_rows(rx, n, k), S.onehash_rows(ry, n, k)
        return E.onehash_intersection(mx, my, S.onehash_values(mx, n),
                                      S.onehash_values(my, n), sizes, sizes, n)

    est = estimate(rx, ry)
    t = float(bounds.minhash_error_scale(size, k, delta))
    share = float(np.mean(np.abs(np.asarray(est) - inter) > t))
    assert share <= delta + 3 * np.sqrt(delta * (1 - delta) / TRIALS), share
