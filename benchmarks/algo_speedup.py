"""Paper Figs. 4–6: speedup / accuracy / memory of PG-enhanced algorithms
vs the tuned exact baselines (TC, 4-clique, clustering, vertex similarity).

Speedup = exact_time / pg_time on identical jit'd paths; accuracy =
|count_PG − count_EX|/count_EX (the paper's metric); memory = sketch bytes
relative to CSR bytes.
"""
from __future__ import annotations

import functools

import numpy as np
import jax

from repro.core import graph as G, sketches as S
from repro.core import exact as X
from repro.core import triangle_count, four_clique_count, jarvis_patrick
from repro.core.intersect import make_pair_cardinality_fn

from .common import emit, timeit


def _sketch_bytes(sk: S.SketchSet) -> int:
    return sk.data.size * sk.data.dtype.itemsize


def _csr_bytes(g: G.Graph) -> int:
    return (2 * g.m + g.n + 1) * 4


def run(budget: float = 0.25):
    graphs = {
        "kron_s12": G.kronecker(12, 16, seed=2),
        "community": G.random_bipartite_community(2000, 8, 0.08, 0.002, seed=4),
    }
    for gname, g in graphs.items():
        # --- Triangle counting (graph/sketch passed as args: no folding)
        tc_exact_fn = jax.jit(X.exact_triangle_count)
        t_exact = timeit(tc_exact_fn, g, iters=3)
        tc_exact = float(tc_exact_fn(g))
        for kind, b in [("bf", 2), ("kh", 1), ("1h", 1)]:
            sk = S.build(g, kind, budget, num_hashes=b, seed=7)
            fn = jax.jit(triangle_count)
            t_pg = timeit(fn, g, sk, iters=3)
            acc = abs(float(fn(g, sk)) - tc_exact) / max(tc_exact, 1)
            emit(f"fig4_tc_{gname}_{kind}", t_pg,
                 f"speedup={t_exact / t_pg:.2f};rel_err={acc:.3f};"
                 f"mem_ratio={_sketch_bytes(sk) / _csr_bytes(g):.3f}")

        # --- Clustering (common neighbors + jaccard + overlap)
        for sim, thr in [("common", 2.0), ("jaccard", 0.05), ("overlap", 0.3)]:
            ex_fn = jax.jit(functools.partial(jarvis_patrick, similarity=sim,
                                              threshold=thr))
            t_ex = timeit(ex_fn, g, iters=3)
            n_ex = int(ex_fn(g)[1])
            sk = S.build(g, "bf", budget, num_hashes=2, seed=7)
            pg_fn = jax.jit(functools.partial(jarvis_patrick, similarity=sim,
                                              threshold=thr))
            t_pg = timeit(pg_fn, g, sk, iters=3)
            n_pg = int(pg_fn(g, sk)[1])
            emit(f"fig4_cluster_{sim}_{gname}_bf", t_pg,
                 f"speedup={t_ex / t_pg:.2f};rel_count={n_pg / max(n_ex, 1):.2f}")

    # --- 4-clique counting (not jitted whole: the triangle list reads its
    # size on the host between its count and fill passes)
    g4 = G.kronecker(9, 10, seed=5)
    t_ex4 = timeit(four_clique_count, g4, iters=2)
    c_ex = float(four_clique_count(g4))
    for kind, b in [("bf", 2), ("kh", 1)]:
        sk = S.build(g4, kind, budget, num_hashes=b, seed=7)
        t_pg4 = timeit(four_clique_count, g4, sk, iters=2)
        acc = abs(float(four_clique_count(g4, sk)) - c_ex) / max(c_ex, 1)
        emit(f"fig5_4clique_{kind}", t_pg4,
             f"speedup={t_ex4 / t_pg4:.2f};rel_err={acc:.3f}")


if __name__ == "__main__":
    run()
