"""Reference mining job: Bloom build, per-edge AND estimate, TC, LCC and
Jarvis-Patrick clustering over the same graph the program is given.

Semantics (ProbGraph Listings 1-4): TC = Σ_e |N_u ∩ N_v| / 3 over canonical
edges; the clustering coefficient of v is ``2·t_v / max(d_v·(d_v-1), 1)``
with ``t_v`` the sum of |N_u ∩ N_v| over v's edges; Jarvis-Patrick keeps an
edge when its Jaccard score ``est / max(d_u + d_v - est, 1)`` reaches the
threshold, and labels each vertex by the least id of its component over the
kept edges.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

from . import sketch as S


def component_min_labels(n: int, uv: np.ndarray) -> np.ndarray:
    """Least vertex id of each vertex's component over edges ``uv``."""
    adj = coo_matrix((np.ones(uv.shape[0], np.int8), (uv[:, 0], uv[:, 1])),
                     shape=(n, n))
    _, comp = connected_components(adj, directed=False)
    least = np.full(comp.max() + 1, n, dtype=np.int64)
    np.minimum.at(least, comp, np.arange(n))
    return least[comp]


def job(n: int, uv: np.ndarray, words: int, num_hashes: int, seed: int,
        jp_threshold: float, dtype=np.float64, band: float = 1e-5) -> dict:
    """Every output of one mining job, in ``dtype``.

    ``uv`` is int64[m, 2] canonical (lo < hi) edges sorted by ``lo·n + hi``.
    Jarvis-Patrick edges whose score lies within a relative ``band`` of the
    threshold are ambiguous to rounding, and either decision is right:
    ``jp_sure`` marks the edges kept for certain, ``jp_ambiguous`` the
    others that may be (see :func:`jp_labels_accepted`).
    """
    sk = S.bloom_of_graph(n, uv, words, num_hashes, seed)
    ones = S.and_ones(sk, uv[:, 0], uv[:, 1])
    cards = S.and_estimate(ones, words * 32, num_hashes, dtype)
    deg = np.bincount(uv.ravel(), minlength=n).astype(np.float64)
    c64 = cards.astype(np.float64)
    tv = np.bincount(uv[:, 0], c64, n) + np.bincount(uv[:, 1], c64, n)
    lcc = tv / np.maximum(deg * (deg - 1.0), 1.0)
    du, dv = deg[uv[:, 0]], deg[uv[:, 1]]
    score = c64 / np.maximum(du + dv - c64, 1.0)
    near = np.abs(score - jp_threshold) <= band * jp_threshold
    return {"sketch": sk, "cards": cards, "tc": float(c64.sum() / 3.0),
            "lcc": lcc, "jp_sure": (score >= jp_threshold) & ~near,
            "jp_ambiguous": near}


def jp_labels_accepted(n: int, uv: np.ndarray, sure: np.ndarray,
                       ambiguous: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """The reference labels that ``labels`` must equal.

    Any subset S of the ambiguous edges may be kept. Take S* = the ambiguous
    edges whose endpoints ``labels`` puts together: if ``labels`` came from
    some S, every edge of S is in S* and every other edge of S* joins one
    component already, so the components of sure ∪ S* are exactly those of
    sure ∪ S; if ``labels`` came from no S, they differ somewhere.
    """
    together = labels[uv[:, 0]] == labels[uv[:, 1]]
    return component_min_labels(n, uv[sure | (ambiguous & together)])
