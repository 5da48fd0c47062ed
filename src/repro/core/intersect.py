"""Uniform |N_u ∩ N_v| providers: exact or any ProbGraph estimator.

`make_pair_cardinality_fn(graph, sketch)` returns a batched pure function
pairs[P,2] -> float32[P] — the paper's "plug in PG routines in place of
exact set intersections" (Listing 6). Estimator *selection* lives here;
*execution* (chunking, padding, degree-ordered layout, kernel block shapes,
edge sharding) is the batched mining engine's job: algorithms consume this
seam through `repro.engine` and an `EnginePlan`.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import estimators as est
from .exact import exact_pair_cardinalities
from .graph import Graph
from .sketches import SketchSet, onehash_values

CardFn = Callable[[jax.Array], jax.Array]


def make_pair_cardinality_fn(graph: Graph, sketch: Optional[SketchSet] = None,
                             *, use_kernel: bool = False,
                             variant: str = "union",
                             estimator: Optional[str] = None,
                             block_e: int = 256, block_w: int = 512) -> CardFn:
    """Build the batched pairs[P, 2] -> float32[P] cardinality provider."""
    if sketch is None:
        def exact_fn(pairs: jax.Array) -> jax.Array:
            return exact_pair_cardinalities(graph, pairs).astype(jnp.float32)
        return exact_fn

    kind = estimator or sketch.kind
    deg = graph.deg

    if sketch.kind == "bf":
        # Both dispatch paths (fused Pallas pass / jnp gather) are lowerings
        # of the same compiled set expression, so their integer popcounts —
        # and therefore the float estimates — are bit-identical. The lazy
        # import keeps the core -> engine edge out of module load order.
        from ..engine import setexpr

        data = sketch.data
        b = sketch.num_hashes
        total_bits = data.shape[1] * 32
        u_row, v_row = setexpr.rows(2)
        expr = (u_row | v_row) if kind == "bf_or" else (u_row & v_row)
        ce = setexpr.compile_expr(expr, block_e=block_e, block_w=block_w,
                                  use_kernel=use_kernel)

        def bf_fn(pairs: jax.Array) -> jax.Array:
            """Per-pair BF estimate from the compiled expression's ones."""
            ones = ce.ones(data, pairs)
            if kind == "bf_l":
                return ones.astype(jnp.float32) / b
            if kind == "bf_or":
                du = jnp.take(deg, pairs[:, 0]).astype(jnp.float32)
                dv = jnp.take(deg, pairs[:, 1]).astype(jnp.float32)
                union_est = est.bf_intersection_and_from_ones(
                    ones, total_bits, b)
                return du + dv - union_est
            return est.bf_intersection_and_from_ones(ones, total_bits, b)
        return bf_fn

    if sketch.kind == "kh":
        def kh_fn(pairs: jax.Array) -> jax.Array:
            ru = jnp.take(sketch.data, pairs[:, 0], axis=0)
            rv = jnp.take(sketch.data, pairs[:, 1], axis=0)
            du = jnp.take(deg, pairs[:, 0])
            dv = jnp.take(deg, pairs[:, 1])
            return est.khash_intersection(ru, rv, du, dv, sketch.n)
        return kh_fn

    if sketch.kind == "1h":
        def oneh_fn(pairs: jax.Array) -> jax.Array:
            ru = jnp.take(sketch.data, pairs[:, 0], axis=0)
            rv = jnp.take(sketch.data, pairs[:, 1], axis=0)
            du = jnp.take(deg, pairs[:, 0])
            dv = jnp.take(deg, pairs[:, 1])
            hu = onehash_values(ru, sketch.n, sketch.seed)
            hv = onehash_values(rv, sketch.n, sketch.seed)
            return est.onehash_intersection(ru, rv, hu, hv, du, dv, sketch.n, variant)
        return oneh_fn

    if sketch.kind == "kmv":
        def kmv_fn(pairs: jax.Array) -> jax.Array:
            ru = jnp.take(sketch.data, pairs[:, 0], axis=0)
            rv = jnp.take(sketch.data, pairs[:, 1], axis=0)
            du = jnp.take(deg, pairs[:, 0])
            dv = jnp.take(deg, pairs[:, 1])
            return est.kmv_intersection(ru, rv, du, dv)
        return kmv_fn

    raise ValueError(f"unknown sketch kind {sketch.kind}")
