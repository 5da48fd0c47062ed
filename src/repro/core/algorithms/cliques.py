"""k-Clique Counting (paper Listing 2, reformulated to expose |X∩Y∩Z|).

Formulation: enumerate ordered triangles u<v<w (edge (u,v) × wedge w∈N_v,
w>v, plus the closing test w∈N_u), then

    cc4 = (1/4) Σ_{triangles u<v<w} |N_u ∩ N_v ∩ N_w|

since each 4-clique {a<b<c<d} contains 4 triangles and the 4th vertex is
counted by the triple intersection exactly once per triangle (self-ids are
excluded automatically: u ∉ N_u). Triple intersections:

  exact : two chained gallops                   O(d log d) / wedge
  BF    : popcount(Bu AND Bv AND Bw), Eq. 2     O(B/W)     / wedge
  kH    : 3-way aligned matches; |∩3| = J3(S1−S2)/(1−J3) with pairwise
          MinHash estimates plugged in          O(k)       / wedge

The closing test w∈N_u uses the BF membership query when a BF sketch is
given (fully sketch-resident, like the paper's set-centric formulation) and
an exact binary search otherwise.

Chunking/padding is the engine's (``EnginePlan``); on the BF kernel path the
per-chunk wedge triples flatten into one (u, v, w) list and the triple
popcounts come from the compiled 3-way AND set expression — identical
integer popcounts to the jnp gather, so estimates are bit-identical.

``five_clique_count`` extends the same scheme one level: enumerate 4-cliques
u<v<w<x from each canonical edge (both w and x drawn from N_v, closed
against N_u and each other), then

    cc5 = (1/5) Σ_{4-cliques u<v<w<x} |N_u ∩ N_v ∩ N_w ∩ N_x|

with the 4-way intersection served by the engine's compiled 4-way AND
expression (``eng.wedge_quad_ones``) — the first workload that needed no
new hand-rolled kernel. See ``core.bounds.bf_kway_and_mse_bound`` for why
the direct k-way AND estimator is preferred over 2^k−1-term
inclusion–exclusion.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ... import engine as eng
from .. import estimators as est
from ..graph import Graph
from ..sketches import SketchSet, bloom_membership
from ..estimators import khash_jaccard, minhash_intersection


def four_clique_count(graph: Graph, sketch: Optional[SketchSet] = None,
                      plan: Optional[eng.EnginePlan] = None,
                      exact_closing_test: bool = False, **kw) -> jax.Array:
    """Scalar 4-clique count: (1/4) Σ_{triangles u<v<w} |N_u ∩ N_v ∩ N_w|."""
    n, d_max = graph.n, graph.d_max
    adj, deg = graph.adj, graph.deg

    kind = sketch.kind if sketch is not None else "exact"
    if plan is None:
        # wedge chunks are [C, d_max]-shaped, so default far below the
        # pair-fold chunk; an explicit plan's edge_chunk wins untouched
        kw.setdefault("edge_chunk", 1024)
    plan = eng.resolve_plan(plan, graph, sketch, kw)

    def wedge_values(pairs, mask):
        """For an edge chunk [C,2]: sum over qualifying wedges of |∩3|."""
        u, v = pairs[:, 0], pairs[:, 1]
        nv = jnp.take(adj, v, axis=0)                      # [C, d_max] candidates w
        w_ok = (nv < n) & (nv > v[:, None]) & mask[:, None]

        # closing test: w ∈ N_u
        if kind == "bf" and not exact_closing_test:
            rows_u = jnp.take(sketch.data, u, axis=0)
            total_bits = sketch.data.shape[1] * 32
            member = jax.vmap(
                lambda row, cand: bloom_membership(row, cand, n, sketch.num_hashes,
                                                   total_bits, sketch.seed)
            )(rows_u, nv)
        else:
            rows_adj_u = jnp.take(adj, u, axis=0)
            pos = jnp.clip(jax.vmap(jnp.searchsorted)(rows_adj_u, nv), 0, d_max - 1)
            member = jnp.take_along_axis(rows_adj_u, pos, axis=1) == nv
        tri = w_ok & member                                # [C, d_max] triangle mask

        if kind == "exact":
            # |N_u ∩ N_v ∩ N_w| via chained gallops
            rows_u_adj = jnp.take(adj, u, axis=0)
            rows_v_adj = jnp.take(adj, v, axis=0)
            posv = jnp.clip(jax.vmap(jnp.searchsorted)(rows_v_adj, rows_u_adj), 0, d_max - 1)
            inter_uv = jnp.where(
                (jnp.take_along_axis(rows_v_adj, posv, axis=1) == rows_u_adj)
                & (rows_u_adj < n), rows_u_adj, n)          # [C, d_max] elements
            w_rows = jnp.take(adj, jnp.where(tri, nv, 0), axis=0)  # [C,d_max,d_max]
            posw = jnp.clip(
                jax.vmap(jax.vmap(jnp.searchsorted, in_axes=(0, None)))(w_rows, inter_uv),
                0, d_max - 1)
            hits = (jnp.take_along_axis(w_rows, posw, axis=2)
                    == inter_uv[:, None, :]) & (inter_uv[:, None, :] < n)
            triple = jnp.sum(hits, axis=2).astype(jnp.float32)    # [C, d_max]
        elif kind == "bf":
            b = sketch.num_hashes
            total_bits = sketch.data.shape[1] * 32
            w_safe = jnp.where(tri, nv, 0)
            # engine's 3-way popcount provider: fused Pallas pass when
            # planned, broadcast jnp gather otherwise
            ones = eng.wedge_triple_ones(sketch, u, v, w_safe, plan)
            triple = est.bf_intersection_and_from_ones(ones, total_bits, b)
        elif kind == "kh":
            mu = jnp.take(sketch.data, u, axis=0)[:, None, :]
            mv = jnp.take(sketch.data, v, axis=0)[:, None, :]
            mw = jnp.take(sketch.data, jnp.where(tri, nv, 0), axis=0)
            k = sketch.k
            valid3 = (mu < n) & (mv < n) & (mw < n)
            j3 = jnp.sum((mu == mv) & (mv == mw) & valid3, axis=-1).astype(jnp.float32) / k
            du = jnp.take(deg, u).astype(jnp.float32)[:, None]
            dv = jnp.take(deg, v).astype(jnp.float32)[:, None]
            dw = jnp.take(deg, jnp.where(tri, nv, 0)).astype(jnp.float32)
            s1 = du + dv + dw
            # pairwise estimates for inclusion-exclusion
            iuv = minhash_intersection(khash_jaccard(mu, mv, n), du, dv)
            iuw = minhash_intersection(khash_jaccard(mu, mw, n), du, dw)
            ivw = minhash_intersection(khash_jaccard(mv, mw, n), dv, dw)
            s2 = iuv + iuw + ivw
            j3 = jnp.minimum(j3, 0.999)
            triple = jnp.maximum(j3 * (s1 - s2) / (1.0 - j3), 0.0)
        else:
            raise ValueError(f"4-clique not supported for sketch kind {kind}")

        return jnp.sum(jnp.where(tri, triple, 0.0))

    return eng.fold_edges(graph.edges, wedge_values, plan) / 4.0


def five_clique_count(graph: Graph, sketch: Optional[SketchSet] = None,
                      plan: Optional[eng.EnginePlan] = None,
                      exact_closing_test: bool = False, **kw) -> jax.Array:
    """Scalar 5-clique count via 4-way sketch intersections.

    Enumerates each 4-clique {u<v<w<x} exactly once from its canonical edge
    (u, v): both w and x are drawn from N_v (they must neighbor v), closed
    against N_u and against each other, with v < w < x. Then

        cc5 = (1/5) Σ_{4-cliques} |N_u ∩ N_v ∩ N_w ∩ N_x|

    since each 5-clique contains five 4-cliques and the fifth vertex is in
    the 4-way intersection exactly once per 4-clique (u ∉ N_u excludes the
    clique's own vertices). The 4-way intersection is the compiled 4-way
    AND set expression via :func:`repro.engine.engine.wedge_quad_ones` —
    no new kernel. Exact and BF sketch paths; other kinds raise.
    """
    n, d_max = graph.n, graph.d_max
    adj = graph.adj

    kind = sketch.kind if sketch is not None else "exact"
    if kind not in ("exact", "bf"):
        raise ValueError(f"5-clique not supported for sketch kind {kind}")
    if plan is None:
        # wedge-pair chunks are [C, d_max, d_max]-shaped, one order heavier
        # than the 4-clique wedges; an explicit plan's edge_chunk wins
        kw.setdefault("edge_chunk", 256)
    plan = eng.resolve_plan(plan, graph, sketch, kw)

    def wedge_pair_values(pairs, mask):
        """For an edge chunk [C,2]: sum over qualifying 4-cliques of |∩4|."""
        u, v = pairs[:, 0], pairs[:, 1]
        nv = jnp.take(adj, v, axis=0)                # [C, d] candidates w, x
        w_ok = (nv < n) & (nv > v[:, None]) & mask[:, None]
        safe = jnp.where(nv < n, nv, 0)

        # closing tests: candidate ∈ N_u, and x ∈ N_w for candidate pairs
        if kind == "bf" and not exact_closing_test:
            total_bits = sketch.data.shape[1] * 32
            rows_u = jnp.take(sketch.data, u, axis=0)
            member_u = jax.vmap(
                lambda row, cand: bloom_membership(
                    row, cand, n, sketch.num_hashes, total_bits, sketch.seed)
            )(rows_u, nv)
            rows_w = jnp.take(sketch.data, safe, axis=0)      # [C, d, words]
            adj_wx = jax.vmap(jax.vmap(
                lambda row, cand: bloom_membership(
                    row, cand, n, sketch.num_hashes, total_bits, sketch.seed),
                in_axes=(0, None)))(rows_w, nv)               # [C, d, d]
        else:
            rows_adj_u = jnp.take(adj, u, axis=0)
            pos = jnp.clip(jax.vmap(jnp.searchsorted)(rows_adj_u, nv),
                           0, d_max - 1)
            member_u = jnp.take_along_axis(rows_adj_u, pos, axis=1) == nv
            w_rows = jnp.take(adj, safe, axis=0)              # [C, d, cap]
            posx = jnp.clip(
                jax.vmap(jax.vmap(jnp.searchsorted,
                                  in_axes=(0, None)))(w_rows, nv),
                0, d_max - 1)
            adj_wx = (jnp.take_along_axis(w_rows, posx, axis=2)
                      == nv[:, None, :]) & (nv[:, None, :] < n)
        tri = w_ok & member_u                                 # [C, d]
        # 4-clique mask over candidate pairs (i -> w, j -> x): both close
        # the (u, v) edge, x > w orders the pair, (w, x) must be an edge
        quad = (tri[:, :, None] & tri[:, None, :]
                & (nv[:, None, :] > nv[:, :, None]) & adj_wx)  # [C, d, d]

        if kind == "exact":
            rows_u_adj = jnp.take(adj, u, axis=0)
            rows_v_adj = jnp.take(adj, v, axis=0)
            posv = jnp.clip(
                jax.vmap(jnp.searchsorted)(rows_v_adj, rows_u_adj),
                0, d_max - 1)
            inter_uv = jnp.where(
                (jnp.take_along_axis(rows_v_adj, posv, axis=1) == rows_u_adj)
                & (rows_u_adj < n), rows_u_adj, n)            # [C, cap]
            w_adj = jnp.take(adj, safe, axis=0)               # [C, d, cap]
            pos4 = jnp.clip(
                jax.vmap(jax.vmap(jnp.searchsorted,
                                  in_axes=(0, None)))(w_adj, inter_uv),
                0, d_max - 1)
            # hits[c, i, e]: does element e of N_u ∩ N_v also neighbor
            # candidate i? |∩4| for pair (i, j) is then Σ_e hits_i · hits_j
            hits = ((jnp.take_along_axis(w_adj, pos4, axis=2)
                     == inter_uv[:, None, :])
                    & (inter_uv[:, None, :] < n)).astype(jnp.float32)
            quad_val = jnp.einsum("cie,cje->cij", hits, hits)
        else:
            b = sketch.num_hashes
            total_bits = sketch.data.shape[1] * 32
            w_safe = jnp.where(tri, nv, 0)
            ones = eng.wedge_quad_ones(sketch, u, v, w_safe, w_safe, plan)
            quad_val = est.bf_intersection_and_from_ones(ones, total_bits, b)

        return jnp.sum(jnp.where(quad, quad_val, 0.0))

    return eng.fold_edges(graph.edges, wedge_pair_values, plan) / 5.0
