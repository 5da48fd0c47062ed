"""Paper Figs. 8–9 (scaling). The container has one core, so thread-count
strong scaling is not measurable; we report the two scaling axes we can:

  * work scaling: wall time vs edge count on Kronecker graphs (weak-scaling
    proxy; the paper grows m with threads). Exact galloping degrades with
    the d_max growth of power-law graphs while PG stays ~linear in m —
    the load-balance argument of Fig. 1 panel 5 in measurable form.
  * device scaling: the engine's edge-sharded TC fold on 1..8 fake host
    devices (launch.mine) is exercised in tests/test_system.py; on real
    hardware that path is the strong-scaling story.
"""
from __future__ import annotations

import functools

import jax

from repro.core import graph as G, sketches as S
from repro.core import exact as X
from repro.core import triangle_count

from .common import emit, timeit


def run():
    for scale in (10, 11, 12, 13):
        g = G.kronecker(scale, 16, seed=2)
        ex = jax.jit(X.exact_triangle_count)
        t_ex = timeit(ex, g, iters=2)
        sk = S.build(g, "bf", 0.25, num_hashes=2, seed=7)
        pg = jax.jit(triangle_count)
        t_pg = timeit(pg, g, sk, iters=2)
        emit(f"fig8_weak_s{scale}", t_pg,
             f"m={g.m};dmax={g.d_max};exact_us={t_ex:.0f};speedup={t_ex/t_pg:.2f}")


if __name__ == "__main__":
    run()
