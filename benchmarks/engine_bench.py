"""Batched mining engine benchmarks: session amortization + edge layout.

Measures (a) the multi-query session win — TC + LCC + clustering over ONE
shared sketch build and ONE per-edge cardinality pass vs three independent
runs — and (b) the degree-ordered edge layout's effect on the fold. Kernel
speed itself is a TPU number (CPU runs interpret mode); here we time the
XLA-compiled jnp paths that share the engine's op structure.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import engine as eng
from repro.core import graph as G, sketches as S
from repro.core import triangle_count, pair_similarity
from repro.core.algorithms.tc import local_clustering_coefficient
from .common import emit, timeit


def run(scale: int = 12, budget: float = 1.0):
    # budget 1.0 makes the per-edge pass the dominant cost, so the session's
    # pass-sharing is what the number measures (not Python dispatch)
    g = G.kronecker(scale, 12, seed=3)
    sk = S.build(g, "bf", budget, num_hashes=2, seed=0)
    jax.block_until_ready(sk.data)

    # independent runs: each query recomputes the per-edge cardinality pass
    def independent():
        a = triangle_count(g, sk)
        b = local_clustering_coefficient(g, sk)
        c = pair_similarity(g, g.edges, "jaccard", sk)
        return a, b, c

    us_indep = timeit(independent, iters=5)

    # session: one shared per-edge pass feeds all three queries
    def shared():
        sess = eng.session(g, sk)
        a = sess.triangle_count()
        b = sess.local_clustering()
        c = sess.edge_similarity("jaccard")
        return a, b, c

    us_sess = timeit(shared, iters=5)
    emit(f"engine_session_tc_lcc_sim_s{scale}", us_sess,
         f"independent_us={us_indep:.1f};amortization={us_indep / us_sess:.2f}x")

    # degree-ordered vs natural edge layout for the fold (jnp path)
    for order in (False, True):
        plan = eng.EnginePlan(edge_chunk=16384, degree_order=order)
        fn = jax.jit(lambda: eng.sum_edge_cardinalities(g, sk, plan)
                     ).lower().compile()
        us = timeit(lambda: fn(), iters=3)
        emit(f"engine_fold_s{scale}_order{int(order)}", us, f"edges={g.m}")

    # one-shot session wall time including sketch build (serving cold start)
    t0 = time.perf_counter()
    sess = eng.session(g, "bf", storage_budget=budget)
    jax.block_until_ready(sess.edge_cardinalities())
    emit(f"engine_cold_session_s{scale}", (time.perf_counter() - t0) * 1e6,
         f"sketch_mb={sess.stats()['sketch_bytes'] / 1e6:.2f}")


if __name__ == "__main__":
    run()
