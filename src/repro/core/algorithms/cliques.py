"""k-Clique Counting (paper Listing 2, reformulated to expose |X∩Y∩Z|).

Four-cliques come from one exact triangle list:

    cc4 = (1/4) Σ_{triangles a,b,c} |N_a ∩ N_b ∩ N_c|

since each 4-clique contains 4 triangles and its 4th vertex is counted by
the triple intersection exactly once per triangle (self-ids are excluded
automatically: a ∉ N_a).

Triangle listing (:func:`triangle_list`) follows k-clique listing in degree
order (Danisch et al., WWW 2018): every edge points from its lower to its
higher (degree, id) rank (``graph.degree_oriented_csr``), and each triangle
is found once, as a < b < c in that rank, from an oriented edge a→b and a
vertex c of N⁺(b) ∩ N⁺(a). The closing test is exact: both oriented rows,
padded to the power of two above the longer one (no row is longer than
√(2m)), are compared all against all. The work follows the oriented
wedges Σ_{a→b} |N⁺(b)| up to that padding, never a padded [edges, d_max]
grid, and it is row gathers and vector compares rather than per-wedge
index arithmetic, which a TPU serves an element at a time. The host reads
the longest oriented row (the padded width); a count pass finds T and keeps
each edge's closing vertices; the host reads T (and the most triangles on
one edge); a fill pass writes them into a ``pow2_bucket(T)``-row list.
``exact_closing_test=False`` tests c against a's Bloom row instead
(sketch resident, like the paper's set-centric formulation; false
positives add triangles that are not there, most of all when a's row is
saturated).

Triple intersections over the list:

  exact : |N_a ∩ N_b ∩ N_c| by searching a's adjacency row in b's and c's
  BF    : popcount(Ba AND Bb AND Bc), Eq. 2, through the engine's compiled
          3-way AND set expression (``engine.triple_cardinality_ones``):
          the fused Pallas pass (``plan.use_kernel``) and the jnp gather give
          identical integer popcounts, so estimates are bit-identical;
          ``return_ones`` also returns their exact Σ, from the same pass
  kH    : 3-way aligned matches; |∩3| = J3(S1−S2)/(1−J3) with pairwise
          MinHash estimates plugged in

Every pass is a module-level ``jax.jit`` whose static arguments come from
the graph's shape and those host reads, so a warm process compiles nothing
for a new session on the same graph.

``five_clique_count`` extends the older edge-fold scheme one level:
enumerate 4-cliques u<v<w<x from each canonical edge (both w and x drawn
from N_v, closed against N_u and each other), then

    cc5 = (1/5) Σ_{4-cliques u<v<w<x} |N_u ∩ N_v ∩ N_w ∩ N_x|

with the 4-way intersection served by the engine's compiled 4-way AND
expression (``eng.wedge_quad_ones``) — the first workload that needed no
new hand-rolled kernel. See ``core.bounds.bf_kway_and_mse_bound`` for why
the direct k-way AND estimator is preferred over 2^k−1-term
inclusion–exclusion.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ... import engine as eng
from ...obs import trace
from .. import estimators as est
from ..graph import Graph, degree_oriented_csr, running
from ..hashing import hash_u32
from ..sketches import SketchSet, bloom_membership
from ..estimators import khash_jaccard, minhash_intersection

_GOLDEN = 0x9E3779B9

#: candidate slots (edges × row width) per step of the triangle listing, and
#: the most edges one step takes
SLOT_CHUNK = 1 << 20
EDGE_CHUNK = 1 << 13


def _n_chunks(total, chunk: int):
    return (total + chunk - 1) // chunk


def _width_class(x: jax.Array) -> jax.Array:
    """⌈log2 x⌉ for x >= 1: the class of a row padded to 2**class."""
    return 32 - jax.lax.clz(x - 1)


@jax.jit
def _edge_classes(oindptr, osrc, odst):
    """The oriented edges grouped by width class (the class of the longer
    of their endpoints' oriented rows), by a counting sort; the first
    position of each class, the number of oriented wedges a→b→c and the
    longest oriented row."""
    m = osrc.shape[0]
    outdeg = oindptr[1:] - oindptr[:-1]
    da, db = jnp.take(outdeg, osrc), jnp.take(outdeg, odst)
    cls = _width_class(jnp.maximum(jnp.maximum(da, db), 1))
    member = cls[:, None] == jnp.arange(math.isqrt(2 * m).bit_length() + 1)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              running(jnp.sum(member, axis=0))])
    pos = jnp.sum(jnp.where(member, starts[None, :-1], 0), axis=1)
    for k in range(member.shape[1]):
        pos = pos + jnp.where(member[:, k], running(member[:, k]) - 1, 0)
    a = jnp.zeros(m, jnp.int32).at[pos].set(osrc, unique_indices=True)
    b = jnp.zeros(m, jnp.int32).at[pos].set(odst, unique_indices=True)
    return a, b, starts, jnp.sum(db), jnp.max(outdeg)


@functools.partial(jax.jit, static_argnames=("width", "num_hashes", "seed"))
def _closing_vertices(oindptr, osrc, odst, a, b, starts, bloom, *, width,
                      num_hashes, seed):
    """For each class-sorted oriented edge a→b, the vertices c of N⁺(b) that
    close a triangle (c ∈ N⁺(a), or in a's Bloom row when ``bloom`` is
    given), first in their row: ``(int32[m + EDGE_CHUNK, width], counts
    int32[m + EDGE_CHUNK])``; rows past m are scratch.

    Both endpoints' oriented rows, padded to their class's width, are
    gathered whole and compared all against all, so the work is regular
    row gathers and vector compares: no per-wedge index arithmetic.
    """
    n = oindptr.shape[0] - 1
    m = osrc.shape[0]
    slot = jnp.arange(m, dtype=jnp.int32)
    row_start = running(jnp.where(
        jnp.concatenate([jnp.ones(1, bool), osrc[1:] != osrc[:-1]]), slot, 0),
        "max")
    rows = jnp.full(n * width, n, jnp.int32).at[
        osrc * width + slot - row_start].set(
            odst, indices_are_sorted=True, unique_indices=True
    ).reshape(n, width)
    pad = jnp.zeros(EDGE_CHUNK, jnp.int32)
    a, b = jnp.concatenate([a, pad]), jnp.concatenate([b, pad])
    closing = jnp.zeros((m + EDGE_CHUNK, width), jnp.int32)
    counts = jnp.zeros(m + EDGE_CHUNK, jnp.int32)
    for cls in range(width.bit_length()):
        w = 1 << cls
        chunk = max(1, min(EDGE_CHUNK, SLOT_CHUNK // w))
        start, size = starts[cls], starts[cls + 1] - starts[cls]

        def body(i, carry, w=w, chunk=chunk, start=start, size=size):
            closing, counts = carry
            r0 = start + i * chunk
            ea = jax.lax.dynamic_slice_in_dim(a, r0, chunk)
            eb = jax.lax.dynamic_slice_in_dim(b, r0, chunk)
            cand = jnp.take(rows[:, :w], eb, axis=0)              # [chunk, w]
            live = (i * chunk + jnp.arange(chunk) < size)[:, None] & (cand < n)
            if bloom is None:
                own = jnp.take(rows[:, :w], ea, axis=0)
                hit = jnp.any(cand[:, :, None] == own[:, None, :], axis=-1)
            else:
                own = jnp.take(bloom, ea, axis=0)                 # [chunk, W]
                hit = live
                for h in range(num_hashes):
                    pos = (hash_u32(cand, (h + seed * _GOLDEN) & 0xFFFFFFFF)
                           % jnp.uint32(own.shape[1] * 32)).astype(jnp.int32)
                    word = jnp.take_along_axis(own, pos >> 5, axis=1)
                    hit = hit & ((word >> (pos & 31).astype(jnp.uint32)) & 1
                                 ).astype(jnp.bool_)
            hit = hit & live
            _, first = jax.lax.sort(((~hit).astype(jnp.int32), cand),
                                    dimension=1, num_keys=1, is_stable=True)
            return (jax.lax.dynamic_update_slice(closing, first, (r0, 0)),
                    jax.lax.dynamic_update_slice(
                        counts, jnp.sum(hit, axis=1, dtype=jnp.int32), (r0,)))

        closing, counts = jax.lax.fori_loop(0, _n_chunks(size, chunk), body,
                                            (closing, counts))
    return closing, counts


@functools.partial(jax.jit, static_argnames=("capacity", "cols"))
def _triangle_rows(a, b, closing, counts, *, capacity, cols):
    """The flat list: int32[capacity, 3] rows (a, b, c), zero past T.

    Chunk by chunk of edges, sorted by their triangle count (largest
    first), column j of the closing vertices holds its triangles in a
    prefix, so the columns are written one after another, each at the end
    of the one before, and the chunks one after another.
    """
    m = a.shape[0]
    chunks = _n_chunks(m, EDGE_CHUNK)
    pad = chunks * EDGE_CHUNK - m

    def padded(x):
        return jnp.concatenate([x[:m], jnp.zeros(pad, jnp.int32)])

    a, b, counts = padded(a), padded(b), padded(counts)
    per_chunk = jnp.sum(counts.reshape(chunks, EDGE_CHUNK), axis=1)
    base = running(per_chunk) - per_chunk

    def chunk(i, out):
        r0 = i * EDGE_CHUNK
        h, ea, eb = (jax.lax.dynamic_slice_in_dim(x, r0, EDGE_CHUNK)
                     for x in (counts, a, b))
        neg, ea, eb, order = jax.lax.sort(
            (-h, ea, eb, jnp.arange(EDGE_CHUNK, dtype=jnp.int32)),
            num_keys=1, is_stable=True)
        by_column = jnp.take(
            jax.lax.dynamic_slice(closing, (r0, 0), (EDGE_CHUNK, cols)),
            order, axis=0).T                             # [cols, EDGE_CHUNK]
        filled = jnp.sum(-neg[None, :] > jnp.arange(cols)[:, None], axis=1,
                         dtype=jnp.int32)
        start = base[i] + running(filled) - filled

        def column(j, out):
            rows = jnp.stack([ea, eb, by_column[j]], axis=1)
            return jax.lax.dynamic_update_slice(out, rows, (start[j], 0))

        return jax.lax.fori_loop(0, -neg[0], column, out)

    out = jax.lax.fori_loop(0, chunks, chunk, jnp.zeros(
        (capacity + EDGE_CHUNK, 3), jnp.int32))
    total = jnp.sum(per_chunk)
    return jnp.where(jnp.arange(capacity)[:, None] < total, out[:capacity], 0)


def triangle_list(graph: Graph, bloom: Optional[SketchSet] = None
                  ) -> Tuple[jax.Array, int, int]:
    """Every triangle once: ``(int32[T_cap, 3], T, wedges)``.

    Row t < T is (a, b, c) with a < b < c in (degree, id) rank order; the
    rows past T are zero, and T_cap = ``pow2_bucket(T)``. ``wedges`` is the
    number of oriented wedges a→b→c examined. ``bloom`` closes each wedge
    with a membership query of c in a's Bloom row instead of the exact
    test. Reads ``indptr``/``indices`` only. The host reads two pairs of
    numbers: the wedge count and the longest oriented row (its padded
    width), then T and the most triangles on one edge.
    """
    if graph.m == 0:
        return jnp.zeros((1, 3), jnp.int32), 0, 0
    oindptr, osrc, odst = degree_oriented_csr(graph.indptr, graph.indices)
    a, b, starts, wedges, longest = _edge_classes(oindptr, osrc, odst)
    wedges, longest = jax.device_get((wedges, longest))
    closing, counts = _closing_vertices(
        oindptr, osrc, odst, a, b, starts,
        bloom.data if bloom is not None else None,
        width=eng.pow2_bucket(int(longest)),
        num_hashes=bloom.num_hashes if bloom is not None else 0,
        seed=bloom.seed if bloom is not None else 0)
    count, widest = jax.device_get((jnp.sum(counts[:graph.m]),
                                    jnp.max(counts)))
    tris = _triangle_rows(a, b, closing, counts,
                          capacity=eng.pow2_bucket(int(count)),
                          cols=eng.pow2_bucket(int(widest)))
    return tris, int(count), int(wedges)


def _sum_chunks(count, chunk: int, values, dtype=jnp.float32) -> jax.Array:
    """Σ over list rows t < count of ``values(first_row)``, the rows of one
    chunk at a time."""
    def body(i, acc):
        live = i * chunk + jnp.arange(chunk) < count
        return acc + jnp.sum(jnp.where(live, values(i * chunk), 0),
                             dtype=dtype)
    return jax.lax.fori_loop(0, _n_chunks(count, chunk), body, dtype(0))


@functools.partial(jax.jit, static_argnames=("plan", "chunk"))
def _bloom_triple_sums(sketch: SketchSet, tris, count, *, plan, chunk):
    """Over the listed triangles, Σ of the AND estimates of |N_a ∩ N_b ∩
    N_c| (float32) and the exact Σ of popcount(B_a & B_b & B_c) as the
    high and low words of a 64-bit count (uint32[2])."""
    def body(i, carry):
        total, hi, lo = carry
        t = jax.lax.dynamic_slice_in_dim(tris, i * chunk, chunk)
        live = i * chunk + jnp.arange(chunk) < count
        ones = eng.triple_cardinality_ones(sketch, t, plan)
        total = total + jnp.sum(jnp.where(
            live, est.bf_intersection_and_from_ones(
                ones, sketch.total_bits, sketch.num_hashes), 0),
            dtype=jnp.float32)
        # a chunk's popcounts stay below 2**32 (four_clique_count bounds
        # chunk · total_bits); a wrap of the low word carries into the high
        low = lo + jnp.sum(jnp.where(live, ones, 0).astype(jnp.uint32),
                           dtype=jnp.uint32)
        return total, hi + (low < lo).astype(jnp.uint32), low

    total, hi, lo = jax.lax.fori_loop(
        0, _n_chunks(count, chunk), body,
        (jnp.float32(0), jnp.uint32(0), jnp.uint32(0)))
    return total, jnp.stack([hi, lo])


@functools.partial(jax.jit, static_argnames=("chunk",))
def _khash_triple_sum(sketch: SketchSet, deg, tris, count, *, chunk):
    """Σ over the listed triangles of the k-hash |N_a ∩ N_b ∩ N_c|."""
    n = sketch.n

    def values(t0):
        t = jax.lax.dynamic_slice_in_dim(tris, t0, chunk)
        mu, mv, mw = (jnp.take(sketch.data, t[:, i], axis=0)
                      for i in range(3))
        valid3 = (mu < n) & (mv < n) & (mw < n)
        j3 = jnp.sum((mu == mv) & (mv == mw) & valid3,
                     axis=-1).astype(jnp.float32) / sketch.k
        du, dv, dw = (jnp.take(deg, t[:, i]).astype(jnp.float32)
                      for i in range(3))
        # pairwise estimates for inclusion-exclusion
        s2 = (minhash_intersection(khash_jaccard(mu, mv, n), du, dv)
              + minhash_intersection(khash_jaccard(mu, mw, n), du, dw)
              + minhash_intersection(khash_jaccard(mv, mw, n), dv, dw))
        j3 = jnp.minimum(j3, 0.999)
        return jnp.maximum(j3 * (du + dv + dw - s2) / (1.0 - j3), 0.0)

    return _sum_chunks(count, chunk, values)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _exact_triple_sum(adj, tris, count, *, chunk):
    """Σ over the listed triangles of |N_a ∩ N_b ∩ N_c|, exactly (int32):
    each entry of a's padded adjacency row is searched in b's and c's."""
    n = adj.shape[0]

    def values(t0):
        t = jax.lax.dynamic_slice_in_dim(tris, t0, chunk)
        ra, rb, rc = (jnp.take(adj, t[:, i], axis=0) for i in range(3))
        hit = ra < n
        for rows in (rb, rc):
            pos = jnp.clip(jax.vmap(jnp.searchsorted)(rows, ra), 0,
                           adj.shape[1] - 1)
            hit = hit & (jnp.take_along_axis(rows, pos, axis=1) == ra)
        return jnp.sum(hit, axis=1, dtype=jnp.int32)

    return _sum_chunks(count, chunk, values, jnp.int32)


def four_clique_count(graph: Graph, sketch: Optional[SketchSet] = None,
                      plan: Optional[eng.EnginePlan] = None,
                      exact_closing_test: bool = True,
                      triangles: Optional[Tuple[jax.Array, int]] = None,
                      return_ones: bool = False, **kw):
    """Scalar 4-clique count: (1/4) Σ_{triangles a,b,c} |N_a ∩ N_b ∩ N_c|.

    ``triangles`` is a list from :func:`triangle_list` (``(rows, T)``, as
    ``MiningSession.triangles()`` caches it); without one the exact list
    is built here. ``exact_closing_test=False`` with a Bloom sketch lists
    the triangles with Bloom-membership closing instead. Triple
    intersections sum over ``plan.edge_chunk``-row chunks of the list
    (rounded down to a power of two). ``return_ones`` (Bloom sketches)
    returns ``(count, ones)`` instead, ``ones`` the exact Σ of the 3-way
    AND popcounts behind the count as uint32[2], its high and low words.
    """
    kind = sketch.kind if sketch is not None else "exact"
    if kind not in ("exact", "bf", "kh"):
        raise ValueError(f"4-clique not supported for sketch kind {kind}")
    if kind != "bf" and (return_ones or not exact_closing_test):
        raise ValueError("return_ones and exact_closing_test=False need a "
                         f"Bloom sketch, not sketch kind {kind}")
    plan = eng.resolve_plan(plan, graph, sketch, kw)
    if not exact_closing_test:
        triangles = triangle_list(graph, bloom=sketch)[:2]
    elif triangles is None:
        triangles = triangle_list(graph)[:2]
    tris, count = triangles
    if count == 0:
        zero = jnp.float32(0)
        return (zero, jnp.zeros(2, jnp.uint32)) if return_ones else zero
    if kind == "exact":
        chunk = min(max(1, (1 << 20) // graph.d_max), tris.shape[0])
        hits = _exact_triple_sum(graph.adj, tris, jnp.int32(count),
                                 chunk=1 << (chunk.bit_length() - 1))
        return hits.astype(jnp.float32) / 4.0
    chunk = min(plan.edge_chunk, tris.shape[0])
    if kind == "bf":
        # one chunk's popcounts must fit the low word of the ones count
        chunk = min(chunk, ((1 << 32) - 1) // sketch.total_bits)
    chunk = 1 << (chunk.bit_length() - 1)
    with trace.span("cliques.triple_and", triples=int(count),
                    words=int(sketch.data.shape[1])) as sp:
        if kind == "kh":
            return sp.fence(_khash_triple_sum(
                sketch, graph.deg, tris, jnp.int32(count), chunk=chunk)) / 4.0
        total, ones = sp.fence(_bloom_triple_sums(
            sketch, tris, jnp.int32(count), plan=plan, chunk=chunk))
    return (total / 4.0, ones) if return_ones else total / 4.0


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
