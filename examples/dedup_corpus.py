"""MinHash near-duplicate document dedup.

The ProbGraph technique applied to a document corpus: k-Hash sketches
over document shingles + the paper's exponential bound
(Prop IV.2) to size k for a target false-match rate, then banded LSH to find
candidates.

Run:  PYTHONPATH=src python examples/dedup_corpus.py
"""
import numpy as np

from repro.data import minhash_dedup
from repro.data.dedup import k_for


def make_corpus(rng, n_docs=60, n_dups=20):
    docs = [rng.integers(0, 5000, size=rng.integers(200, 800)).astype(np.int64)
            for _ in range(n_docs)]
    # near-duplicates: 3% token noise over random originals
    for i in rng.choice(n_docs, size=n_dups, replace=False):
        d = docs[i].copy()
        idx = rng.choice(len(d), size=max(1, len(d) // 33), replace=False)
        d[idx] = rng.integers(0, 5000, size=len(idx))
        docs.append(d)
    return docs, n_docs


def main():
    rng = np.random.default_rng(0)
    docs, n_orig = make_corpus(rng)
    # Prop IV.2: sketch size for ±0.1 Jaccard resolution at 1% failure prob
    k = k_for(j_gap=0.1, delta=0.01)
    print(f"corpus: {len(docs)} docs ({len(docs) - n_orig} planted near-dups)")
    print(f"Prop IV.2 says k={k} for |Ĵ−J| < 0.1 w.p. 99%")

    keep, stats = minhash_dedup(docs, threshold=0.7, k=max(64, k))
    dropped = (~keep).sum()
    dropped_planted = (~keep[n_orig:]).sum()
    print(f"dropped {dropped} docs ({dropped_planted} of the planted dups); "
          f"checked {stats['checked_pairs']} candidate pairs via LSH")
    for a, b, j in stats["dropped_pairs"][:5]:
        print(f"  doc{b} ≈ doc{a} (Ĵ={j:.2f})")
    kept_tokens = sum(len(docs[i]) for i in range(len(docs)) if keep[i])
    print(f"tokens kept: {kept_tokens}")


if __name__ == "__main__":
    main()
