"""Reference 4-clique count: degree-ordered triangle listing and the
direct 3-way Bloom AND estimate over it.

Semantics (ProbGraph arXiv:2208.11469, 4-clique counting with the k-way
Bloom AND): cc4 = Σ_{triangles a,b,c} |N_a ∩ N_b ∩ N_c| / 4, each triple
intersection estimated from the ones of ``B_a & B_b & B_c`` by the
Swamidass AND estimator. Triangles are listed as in k-clique listing
(Danisch et al., WWW 2018): each edge points from its lower to its higher
(degree, id) rank, and a triangle a < b < c in that rank is the oriented
wedge a→b→c closed by the edge a→c. Everything runs in blocks, so the
scale-16 graph (95M oriented wedges, 15.6M triangles) fits in memory.
"""
from __future__ import annotations

import numpy as np

from . import sketch as S


def oriented_edges(n: int, uv: np.ndarray):
    """Edges from lower to higher (degree, id) rank, sorted by (a, b):
    ``(indptr int64[n+1], a int64[m], b int64[m])``."""
    deg = np.bincount(uv.ravel(), minlength=n)
    rank = np.empty(n, dtype=np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    up = rank[uv[:, 0]] < rank[uv[:, 1]]
    a = np.where(up, uv[:, 0], uv[:, 1])
    b = np.where(up, uv[:, 1], uv[:, 0])
    order = np.lexsort((b, a))
    a, b = a[order], b[order]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(a, minlength=n), out=indptr[1:])
    return indptr, a, b


def triangles(n: int, uv: np.ndarray, block: int = 1 << 23):
    """``(int64[T, 3], wedges)``: every triangle once as (a, b, c) in rank
    order, and the number of oriented wedges examined."""
    indptr, a, b = oriented_edges(n, uv)
    if a.size == 0:
        return np.zeros((0, 3), dtype=np.int64), 0
    keys = a * n + b                       # sorted: edges are sorted by (a, b)
    per_edge = indptr[b + 1] - indptr[b]   # wedges a→b→c through each edge
    ends = np.cumsum(per_edge)
    cuts = np.searchsorted(ends, np.arange(block, int(ends[-1]), block),
                           side="right")
    found = []
    for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, a.size]):
        cnt = per_edge[lo:hi]
        wa, wb = np.repeat(a[lo:hi], cnt), np.repeat(b[lo:hi], cnt)
        k = np.arange(wa.size) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        wc = b[indptr[wb] + k]
        q = wa * n + wc
        pos = np.minimum(np.searchsorted(keys, q), keys.size - 1)
        hit = keys[pos] == q
        found.append(np.stack([wa[hit], wb[hit], wc[hit]], axis=1))
    return np.concatenate(found), int(per_edge.sum())


def triangle_keys(n: int, tris: np.ndarray) -> np.ndarray:
    """``a·n² + b·n + c`` per row (int64)."""
    t = np.asarray(tris, dtype=np.int64)
    return (t[:, 0] * n + t[:, 1]) * n + t[:, 2]


def triple_and_ones(sketch: np.ndarray, tris: np.ndarray,
                    block: int = 1 << 18) -> np.ndarray:
    """int64 popcount of ``B_a & B_b & B_c`` per triangle, in blocks."""
    out = np.empty(tris.shape[0], dtype=np.int64)
    for lo in range(0, tris.shape[0], block):
        t = tris[lo:lo + block]
        rows = sketch[t[:, 0]] & sketch[t[:, 1]] & sketch[t[:, 2]]
        out[lo:lo + block] = np.bitwise_count(rows).sum(axis=1,
                                                        dtype=np.int64)
    return out


def pairwise_sum(x: np.ndarray, dtype) -> float:
    """Σ x by pairwise (tree) summation, each partial sum rounded to
    ``dtype``: the most accurate order a ``dtype`` sum can take."""
    x = np.asarray(x, dtype=dtype)
    if x.size == 0:
        return 0.0
    while x.size > 1:
        if x.size % 2:
            x = np.concatenate([x, np.zeros(1, dtype=dtype)])
        x = (x[0::2] + x[1::2]).astype(dtype)
    return float(x[0])


def four_clique_estimate(ones: np.ndarray, total_bits: int, num_hashes: int,
                         dtype=np.float64, sum_dtype=None) -> float:
    """Σ of the per-triangle AND estimates from their popcounts ``ones``,
    over 4: the estimates computed in ``dtype``, their pairwise sum in
    ``sum_dtype`` (``dtype`` when None)."""
    est = S.and_estimate(ones, total_bits, num_hashes, dtype)
    return pairwise_sum(est, sum_dtype or dtype) / 4.0
