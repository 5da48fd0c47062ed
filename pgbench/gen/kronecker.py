"""Graph500 Kronecker edge generator, the benchmark's own copy.

The Graph500 specification draws ``edge_factor · 2**scale`` edges by
descending ``scale`` levels of the 2x2 initiator ``[[A, B], [C, D]]`` with
A = 0.57, B = C = 0.19, then permutes the vertex ids. Kept here, apart from
the program's generator, so that no change to the program can change the
benchmark's data.

A configuration fixes the graph with ``structure_seed``; the run seed
shuffles the order of its edge array and the endpoints of each edge.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of a run seed (any integer)."""
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int) -> np.ndarray:
    """int64[edge_factor · 2**scale, 2] raw Kronecker draws (duplicates and
    self loops included, as the specification draws them)."""
    n_draws = edge_factor << scale
    rng = np.random.default_rng(seed)
    src = np.zeros(n_draws, dtype=np.int64)
    dst = np.zeros(n_draws, dtype=np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for level in range(scale):
        src_bit = rng.random(n_draws) > ab
        dst_bit = rng.random(n_draws) > np.where(src_bit, c_norm, a_norm)
        src |= src_bit.astype(np.int64) << level
        dst |= dst_bit.astype(np.int64) << level
    perm = rng.permutation(1 << scale)
    return np.stack([perm[src], perm[dst]], axis=1)


def canonical_keys(n: int, edges: np.ndarray) -> np.ndarray:
    """Sorted unique keys ``lo·n + hi`` of the undirected simple graph that
    an edge array describes (self loops dropped)."""
    u, v = edges[:, 0].astype(np.int64), edges[:, 1].astype(np.int64)
    keep = u != v
    lo, hi = np.minimum(u[keep], v[keep]), np.maximum(u[keep], v[keep])
    return np.unique(lo * n + hi)


def decode(n: int, keys: np.ndarray) -> np.ndarray:
    """int64[k, 2] (lo, hi) pairs of canonical keys."""
    return np.stack([keys // n, keys % n], axis=1)


def structure(cfg: dict) -> np.ndarray:
    """Canonical keys of the configuration's fixed graph structure."""
    n = 1 << cfg["scale"]
    raw = kronecker_edges(cfg["scale"], cfg["edge_factor"], cfg["a"],
                          cfg["b"], cfg["c"], cfg["structure_seed"])
    return canonical_keys(n, raw)


def shuffled(cfg: dict, seed: int) -> np.ndarray:
    """The run's edge array: the fixed structure's edges in an order, and
    with endpoints swapped, drawn from ``seed``; int64[m, 2].

    The program sees the same simple graph for every seed, so every seed
    does the same work; what changes is the raw input it canonicalizes.
    (Relabelling the vertices instead changed a query job's time on a TPU
    v5e by up to 18 % from seed to seed, against about 1 % between two runs
    of one seed: the scatters' memory locality follows the labels.)
    """
    n = 1 << cfg["scale"]
    uv = decode(n, structure(cfg))
    rng = rng_for(seed, 0)
    uv = uv[rng.permutation(uv.shape[0])]
    swap = rng.random(uv.shape[0]) < 0.5
    uv[swap] = uv[swap][:, ::-1]
    return uv
