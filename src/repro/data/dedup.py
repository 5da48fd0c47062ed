"""MinHash near-duplicate document dedup with the ProbGraph sketches.

Documents -> w-gram shingles -> k-Hash MinHash sketches (core.hashing, same
murmur3 finalizer as the graph sketches) -> LSH banding for candidate pairs
-> Jaccard estimate Ĵ_kH = matches/k -> drop docs with Ĵ ≥ threshold.

The paper's Prop IV.2 bound makes k quantitative:
P(|Ĵ−J| ≥ t) ≤ 2·exp(−2kt²), so ``k_for(j_gap, delta)`` returns the sketch
size guaranteeing false-match probability ≤ delta at a Jaccard margin j_gap.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.core.hashing import np_hash_u32

_GOLDEN = 0x9E3779B9


def k_for(j_gap: float, delta: float) -> int:
    """Smallest k with P(|Ĵ−J| ≥ j_gap) ≤ delta (Hoeffding/Prop IV.2 on Ĵ)."""
    return int(np.ceil(np.log(2.0 / delta) / (2.0 * j_gap ** 2)))


def _shingles(tokens: np.ndarray, w: int) -> np.ndarray:
    """Rolling w-gram hashes of a token array (uint32)."""
    tokens = np.asarray(tokens, dtype=np.uint32)
    if len(tokens) < w:
        return np_hash_u32(tokens, 7)
    h = np.zeros(len(tokens) - w + 1, dtype=np.uint32)
    with np.errstate(over="ignore"):
        for i in range(w):
            h = (h * np.uint32(1000003)) ^ np_hash_u32(tokens[i:i + len(h)], 7 + i)
    return h


def document_sketches(docs: Sequence[np.ndarray], k: int, w: int = 5,
                      seed: int = 0) -> np.ndarray:
    """k-Hash MinHash sketches over shingles: uint32[N, k] (min hash values)."""
    out = np.full((len(docs), k), 0xFFFFFFFF, dtype=np.uint32)
    for di, doc in enumerate(docs):
        sh = _shingles(doc, w)
        if len(sh) == 0:
            continue
        for i in range(k):
            s = np.uint32((i + seed * _GOLDEN) & 0xFFFFFFFF)
            out[di, i] = np_hash_u32(sh, int(s)).min()
    return out


def jaccard_estimate(sk_a: np.ndarray, sk_b: np.ndarray) -> float:
    """Ĵ_kH = aligned matches / k (paper Eq. 5 numerator)."""
    return float(np.mean(sk_a == sk_b))


def minhash_dedup(docs: Sequence[np.ndarray], threshold: float = 0.8,
                  k: int = 64, w: int = 5, bands: int = 0,
                  seed: int = 0) -> Tuple[np.ndarray, Dict]:
    """Returns (keep mask bool[N], stats). Keeps the first doc of each
    near-duplicate group (banded-LSH candidates, Ĵ_kH confirmation)."""
    n = len(docs)
    if bands <= 0:
        bands = max(8, k // 4)   # 4 rows/band: P(candidate) ≈ 1 at J ≥ 0.7
    sketches = document_sketches(docs, k, w, seed)
    rows_per_band = max(1, k // bands)
    buckets: Dict[Tuple[int, bytes], List[int]] = defaultdict(list)
    for di in range(n):
        for b in range(bands):
            band = sketches[di, b * rows_per_band:(b + 1) * rows_per_band]
            buckets[(b, band.tobytes())].append(di)

    keep = np.ones(n, dtype=bool)
    checked = 0
    dropped_pairs = []
    for key, members in buckets.items():
        if len(members) < 2:
            continue
        members = sorted(members)
        anchor = members[0]
        for other in members[1:]:
            if not keep[other] or not keep[anchor]:
                continue
            checked += 1
            j = jaccard_estimate(sketches[anchor], sketches[other])
            if j >= threshold:
                keep[other] = False
                dropped_pairs.append((anchor, other, j))
    stats = {"checked_pairs": checked, "dropped": int((~keep).sum()),
             "dropped_pairs": dropped_pairs[:32], "k": k, "bands": bands}
    return keep, stats
