"""MinHash near-duplicate document dedup."""
import numpy as np

from repro.data import minhash_dedup, document_sketches
from repro.data.dedup import jaccard_estimate, k_for


def _doc(rng, n=400):
    return rng.integers(0, 1000, size=n, dtype=np.int64)


def test_dedup_drops_planted_duplicates():
    rng = np.random.default_rng(0)
    base = [_doc(rng) for _ in range(20)]
    # plant near-duplicates: copy with 2% token noise
    dups = []
    for d in base[:8]:
        d2 = d.copy()
        idx = rng.choice(len(d2), size=len(d2) // 50, replace=False)
        d2[idx] = rng.integers(0, 1000, size=len(idx))
        dups.append(d2)
    docs = base + dups
    keep, stats = minhash_dedup(docs, threshold=0.6, k=64)
    assert keep[:20].all(), "originals kept"
    assert (~keep[20:]).sum() >= 6, f"planted dups should drop: {stats}"


def test_dedup_keeps_distinct_docs():
    rng = np.random.default_rng(1)
    docs = [_doc(rng) for _ in range(30)]
    keep, _ = minhash_dedup(docs, threshold=0.6, k=64)
    assert keep.all()


def test_sketch_jaccard_estimates_true_jaccard():
    rng = np.random.default_rng(2)
    a = _doc(rng, 2000)
    b = a.copy()
    b[:1000] = rng.integers(0, 1000, size=1000)  # ~50% shingle overlap
    sk = document_sketches([a, b], k=256)
    j = jaccard_estimate(sk[0], sk[1])
    assert 0.05 < j < 0.8


def test_k_for_bound_inversion():
    k = k_for(0.1, 0.01)
    # Hoeffding: 2 exp(-2 k t^2) <= delta
    assert 2 * np.exp(-2 * k * 0.1**2) <= 0.0101
