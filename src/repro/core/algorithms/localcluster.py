"""Local graph clustering: batched PPR forward push + sketch-gated sweep cuts.

The seed-centric workload (Andersen–Chung–Lang / PPR-Nibble, parallelized as
in Shun et al. 2016 and frontier-formulated as in GBBS): given seed vertices,
find low-conductance clusters around them without touching the whole graph's
combinatorics. Two phases, both expressed as the regular batched tensor work
the engine already emits:

  1. **Forward push** — approximate personalized PageRank, in one of two
     frontier layouts selected by ``plan.frontier_mode``:

     * **dense** — ``r`` residual and ``p`` estimate as ``[S, n]`` float
       tensors; one synchronous push step activates *every* vertex over the
       ACL threshold at once and propagates mass through an edge-parallel
       scatter-add over ``graph.edges``. Simple and fast while ``[S, n]``
       fits, fatal at web scale.
     * **sparse** — the Shun et al. frontier-sparse formulation: each seed's
       support lives in a capped ``[S, cap]`` index+value table (``idx``
       ascending vertex ids padded with the sentinel ``n``, plus ``p``/``r``
       values), with ``cap = O(1/(alpha·eps))`` from the ACL work bound,
       pow2-bucketed so ragged (alpha, eps) choices reuse compiles. A push
       round gathers the active rows' padded adjacency, then merges table
       and neighbor contributions with one stable sort-by-id + segment
       scatter-add — memory scales with the support times the adjacency
       width (``cap · d_max`` per seed and round), never ``n``. If a
       round ever produces more than ``cap`` distinct support vertices the
       whole batch *spills*: the overflow flag aborts the loop and the
       caller re-runs the dense push. Spill is a performance event, never a
       correctness event (invariant 10 in docs/ARCHITECTURE.md).

     Both layouts implement the same synchronous ACL dynamics, so they agree
     within float associativity (and exactly on support/sweep order in
     practice); every consumer downstream of the push sees one result type.

  2. **Sweep cut** — order vertices by degree-normalized PPR mass and scan
     prefixes ``S_1 ⊂ S_2 ⊂ …``, picking the prefix with minimum conductance
     ``φ(S) = cut(S) / min(vol(S), vol(V∖S))``. The expensive term is the
     per-step ``|N(v_j) ∩ S_{j-1}|`` (cut increment = ``d(v_j) − 2·|N(v_j) ∩
     S_{j-1}|``). The sketch-gated path replaces it with ProbGraph set
     algebra: the swept prefix is itself a Bloom filter (exclusive prefix-OR
     of single-vertex bit rows under the *same* hash family as the
     neighborhood sketch), so every increment is one AND+popcount between
     ``B(N(v_j))`` and ``B(S_{j-1})`` — ``bf_edge_intersect``-style work,
     optionally routed through the Pallas pair kernel. The exact fallback
     counts swept-rank hits through the padded adjacency.

``core.bounds.sweep_cut_rmse`` / ``bloom_words_for_conductance`` make the
sketch knob quantitative: size the Bloom filter from a target conductance
error instead of guessing.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ... import engine as eng
from ...obs import metrics as obs_metrics
from ...obs import trace
from ..estimators import bf_intersection_and_from_ones
from ..graph import Graph
from ..sketches import SketchSet, bloom_rows


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseFrontier:
    """Capped per-seed PPR support: the sparse push's index+value buffers.

    Each seed's support is a row of ``cap`` slots holding ascending vertex
    ids (``idx``; unused slots carry the sentinel ``n``) with the matching
    PPR estimate ``p`` and residual ``r`` values. Memory is ``O(S · cap)``
    with ``cap = O(1/(alpha·eps))`` — independent of ``n``.

    Attributes:
      idx: int32[S, cap]   support vertex ids, ascending per row; pad = n.
      p:   float32[S, cap] PPR estimates aligned with ``idx``.
      r:   float32[S, cap] final residuals aligned with ``idx``.
      iterations: int32    push rounds executed.
      overflowed: bool[]   True when some round needed more than ``cap``
                           distinct support vertices — the buffers are then
                           truncated mid-round and MUST NOT be consumed;
                           callers re-run the dense push (a spill).
      n: static int        vertex count (the id sentinel).
    """

    idx: jax.Array
    p: jax.Array
    r: jax.Array
    iterations: jax.Array
    overflowed: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def cap(self) -> int:
        """Slots per seed (the pow2-bucketed frontier capacity)."""
        return int(self.idx.shape[1])

    def sizes(self):
        """int64[S]: occupied slots (support size) per seed (host-side)."""
        import numpy as np
        return np.sum(np.asarray(self.idx) < self.n, axis=1).astype(np.int64)

    def densify(self):
        """Scatter back to dense ``(p, r)`` float32[S, n] (test/debug aid —
        materializes exactly what the dense push would have produced, up to
        float summation order)."""
        s_batch = self.idx.shape[0]
        rows = jnp.arange(s_batch)[:, None]
        # width n+1 gives sentinel ids a scratch column sliced away below
        p = jnp.zeros((s_batch, self.n + 1), jnp.float32)
        r = jnp.zeros((s_batch, self.n + 1), jnp.float32)
        p = p.at[rows, self.idx].add(self.p, mode="drop")
        r = r.at[rows, self.idx].add(self.r, mode="drop")
        return p[:, :self.n], r[:, :self.n]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LocalClusterResult:
    """Per-seed output of :func:`local_cluster` (a batched sweep).

    Attributes:
      order:       int32[S, k]   sweep order (vertices by descending p/deg;
                                 entries past ``support`` are padding).
      conductance: float32[S, k] conductance of each swept prefix (``inf``
                                 at invalid prefixes: empty, full-volume, or
                                 past the seed's support).
      best_idx:    int32[S]      prefix index minimizing conductance.
      best_conductance: float32[S] the minimum conductance itself (``inf``
                                 when the seed admits no valid prefix).
      best_size:   int32[S]      cluster size = best_idx + 1, or 0 when no
                                 valid prefix exists (isolated seed /
                                 whole-volume support) — ``members`` is
                                 then empty.
      support:     int32[S]      number of vertices with positive PPR mass
                                 that entered the sweep (≤ k).
      ppr:         float32[S, n] the approximate PPR vectors (dense push
                                 output; ``None`` on the sparse path, where
                                 the same data lives in ``frontier``).
      residual:    float32[S, n] the final push residuals (dense path only;
                                 ``None`` on the sparse path).
      frontier:    the :class:`SparseFrontier` buffers (sparse path only;
                                 ``None`` on the dense path).
      iterations:  int32         push iterations until convergence/cap.
      spilled:     static bool   True when the sparse push overflowed its
                                 cap and the answer was recomputed densely —
                                 a performance event, never a correctness
                                 event.
    """

    order: jax.Array
    conductance: jax.Array
    best_idx: jax.Array
    best_conductance: jax.Array
    best_size: jax.Array
    support: jax.Array
    ppr: Optional[jax.Array]
    residual: Optional[jax.Array]
    iterations: jax.Array
    frontier: Optional[SparseFrontier] = None
    spilled: bool = dataclasses.field(default=False,
                                      metadata=dict(static=True))

    def members(self, s: int):
        """Vertex ids of seed ``s``'s best cluster (host-side convenience)."""
        import numpy as np
        k = int(np.asarray(self.best_size)[s])
        return np.asarray(self.order)[s, :k]

    def footprint(self, s: int):
        """Vertex ids seed ``s``'s answer depends on (sorted int64).

        Every vertex that ever held PPR mass or residual during the push:
        the push dynamics read only these vertices' degrees and incident
        edges (a vertex whose residual never crossed the ACL threshold still
        gates on ``r[v] ≥ eps·d(v)``, so its *degree* is load-bearing), and
        the sweep reads only rows/degrees of the swept support — a subset.
        This is the serving-tier cache's invalidation set; conductance
        additionally depends on the total volume ``2m``, which the cache
        guards separately (see ``stream.cache``). On the sparse path the
        set falls out of the index buffer directly (already id-sorted), so
        footprints cost ``O(cap)`` instead of an ``O(n)`` dense scan.
        """
        import numpy as np
        if self.frontier is not None:
            idx = np.asarray(self.frontier.idx[s])
            p = np.asarray(self.frontier.p[s])
            r = np.asarray(self.frontier.r[s])
            keep = (idx < self.frontier.n) & ((p > 0) | (r > 0))
            return idx[keep].astype(np.int64)
        p = np.asarray(self.ppr[s])
        r = np.asarray(self.residual[s])
        return np.nonzero((p > 0) | (r > 0))[0].astype(np.int64)


# ----------------------------------------------------------------------------
# phase 1: batched approximate PPR (ACL forward push, synchronous frontier)
# ----------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "max_iters"))
def _ppr_push_impl(deg: jax.Array, edges: jax.Array, seeds: jax.Array,
                   alpha, eps, *, n: int, max_iters: int):
    """Jitted push body over raw arrays (not the Graph pytree, whose static
    ``n_edges`` would retrace per streaming delta); ``edges`` is pow2-padded
    with sentinel (n, n) rows whose scatter contributions drop."""
    deg = deg.astype(jnp.float32)
    s_batch = seeds.shape[0]
    thresh = eps * jnp.maximum(deg, 1.0)

    p0 = jnp.zeros((s_batch, n), jnp.float32)
    r0 = p0.at[jnp.arange(s_batch), seeds].add(1.0)

    def body(state):
        p, r, it = state
        active = r >= thresh[None, :]
        push = jnp.where(active, r, 0.0)
        # isolated vertices (deg 0) absorb their whole mass into p
        p = p + jnp.where(deg[None, :] > 0, alpha * push, push)
        give = jnp.where(deg[None, :] > 0,
                         (1.0 - alpha) * push / jnp.maximum(deg[None, :], 1.0),
                         0.0)
        # edge-parallel propagate: each canonical edge carries mass both
        # ways; sentinel pad rows scatter out of bounds and are dropped
        recv = jnp.zeros_like(r)
        recv = recv.at[:, edges[:, 1]].add(
            give[:, jnp.minimum(edges[:, 0], n - 1)], mode="drop")
        recv = recv.at[:, edges[:, 0]].add(
            give[:, jnp.minimum(edges[:, 1], n - 1)], mode="drop")
        return p, jnp.where(active, 0.0, r) + recv, it + 1

    def cond(state):
        _, r, it = state
        return jnp.any(r >= thresh[None, :]) & (it < max_iters)

    p, r, iters = jax.lax.while_loop(cond, body, (p0, r0, jnp.int32(0)))
    return p, r, iters


def _padded_edges(graph: Graph) -> jax.Array:
    """graph.edges padded to a pow2 bucket with sentinel (n, n) rows, so the
    jitted push compiles once per size class instead of once per delta."""
    m = graph.edges.shape[0]
    m_b = eng.plan.pow2_bucket(m)
    if m_b == m:
        return graph.edges
    pad = jnp.full((m_b - m, 2), graph.n, graph.edges.dtype)
    return jnp.concatenate([graph.edges, pad], axis=0)


def _padded_seeds(seeds: jax.Array):
    """Pad a seed batch to its pow2 bucket by repeating the first seed.

    Push rows are fully independent (per-row state, per-row updates), and
    the loop's stop condition is a max over rows, so duplicating an existing
    row changes neither the surviving rows' values nor the iteration count —
    slicing the pad rows off afterwards is bit-identical to running the
    ragged batch. This bounds XLA recompiles to one per (n, edge-bucket,
    seed-bucket) class instead of one per distinct ragged batch size.
    """
    s = seeds.shape[0]
    s_b = eng.plan.pow2_bucket(s)
    if s_b == s:
        return seeds, s
    fill = seeds[0] if s else jnp.int32(0)
    pad = jnp.full((s_b - s,), fill, seeds.dtype)
    return jnp.concatenate([seeds, pad]), s


def ppr_push(graph: Graph, seeds: jax.Array, alpha: float = 0.15,
             eps: float = 1e-4, max_iters: int = 200):
    """Batched ACL forward push: approximate PPR for a batch of seeds.

    Args:
      graph:     the (frozen or view) graph; only ``deg`` and ``edges`` are
                 read, so the result is independent of adjacency padding.
      seeds:     int32[S] seed vertex ids (duplicates allowed — pad a batch
                 by repeating any seed and drop the copies).
      alpha:     teleport probability of the underlying random walk.
      eps:       push tolerance — iterate until every residual satisfies
                 ``r[v] < eps·max(d(v), 1)``.
      max_iters: hard cap on synchronous push rounds.

    Returns:
      ``(p, r, iters)``: PPR estimates float32[S, n], final residuals
      float32[S, n], and the int32 number of rounds executed. The ACL
      invariant bounds the truncation: ``p ≤ ppr_exact ≤ p + eps·deg``
      coordinatewise (in exact arithmetic). The implementation is jitted
      with ``alpha``/``eps`` as traced scalars and both the edge list and
      the seed batch padded to pow2 buckets, so repeated serving calls —
      including across streaming deltas, where ``m`` changes every batch,
      and ragged ad-hoc seed batches — reuse one compiled program per
      (n, edge-bucket, seed-bucket) class.
    """
    seeds = jnp.asarray(seeds, jnp.int32).reshape(-1)
    seeds_b, s = _padded_seeds(seeds)
    p, r, iters = _ppr_push_impl(graph.deg, _padded_edges(graph), seeds_b,
                                 jnp.float32(alpha), jnp.float32(eps),
                                 n=graph.n, max_iters=max_iters)
    return p[:s], r[:s], iters


# ----------------------------------------------------------------------------
# phase 1 (sparse): capped-frontier push — memory O(S/(alpha·eps)), not O(S·n)
# ----------------------------------------------------------------------------

# auto mode only goes sparse when a sparse round's buffers undercut the
# dense [S, n] tensors by at least this factor — below that, the dense
# push's simpler rounds win and nothing is at risk of spilling
_AUTO_SPARSE_FACTOR = 8


def frontier_cap_for(alpha: float, eps: float, n: int,
                     override: Optional[int] = None) -> int:
    """Sparse-frontier capacity: pow2 bucket of the ACL support bound.

    The push performs at most ``1/(alpha·eps)`` pushes total (each push on
    ``v`` retires ``≥ alpha·eps·d(v)`` residual mass from an invariant total
    of 1), so the support it can ever touch is ``O(1/(alpha·eps))`` —
    independent of ``n``. The bucket is clamped to ``pow2(n)`` (a cap above
    that buys nothing) and to ≥ 2 so the degenerate single-slot table never
    compiles. ``override`` (``plan.frontier_cap``) replaces the bound but is
    bucketed the same way; undersizing only risks a spill, never a wrong
    answer.
    """
    if override is not None:
        cap = int(override)
    else:
        cap = int(math.ceil(1.0 / (float(alpha) * float(eps))))
    return min(eng.plan.pow2_bucket(cap, lo=2), eng.plan.pow2_bucket(n, lo=2))


def resolve_frontier_mode(plan: eng.EnginePlan, n: int, width: int,
                          alpha: float, eps: float) -> str:
    """Dense-vs-sparse plan selection.

    "auto" compares per-seed round buffers: a sparse round gathers one
    ``width``-wide adjacency row per table slot and merges it with the
    table, ``cap·(width + 1)`` entries, against the dense round's ``n``.
    """
    mode = plan.frontier_mode
    if mode not in ("auto", "dense", "sparse"):
        raise ValueError(f"unknown frontier_mode: {mode!r}")
    if mode != "auto":
        return mode
    cap = frontier_cap_for(alpha, eps, n, plan.frontier_cap)
    return ("sparse" if cap * (width + 1) * _AUTO_SPARSE_FACTOR <= n
            else "dense")


@functools.partial(jax.jit, static_argnames=("n", "cap", "max_iters"))
def _ppr_push_sparse_impl(deg: jax.Array, adj: jax.Array, seeds: jax.Array,
                          alpha, eps, *, n: int, cap: int, max_iters: int):
    """Jitted sparse push: per-seed ``[S, cap]`` id-sorted support tables.

    One round: gather the active entries' padded adjacency rows, then merge
    the table with the neighbor contributions via a stable sort by vertex id
    + segment-head scatter-add (duplicate ids compact into one slot). Ids
    stay ascending per row, so the table doubles as the sorted support set.
    Overflow (> ``cap`` distinct ids after a merge) raises a flag that stops
    the loop; the truncated buffers must then be discarded by the caller.
    """
    deg = deg.astype(jnp.float32)
    s_batch = seeds.shape[0]
    width = adj.shape[1]
    rows = jnp.arange(s_batch)[:, None]

    idx0 = jnp.full((s_batch, cap), n, jnp.int32).at[:, 0].set(seeds)
    p0 = jnp.zeros((s_batch, cap), jnp.float32)
    r0 = p0.at[:, 0].set(1.0)

    def entry_deg(idx):
        """Degrees of table entries; sentinel slots read as degree 0."""
        return jnp.where(idx < n, jnp.take(deg, jnp.minimum(idx, n - 1)), 0.0)

    def body(state):
        idx, p, r, it, ovf = state
        valid = idx < n
        d = entry_deg(idx)
        active = valid & (r >= eps * jnp.maximum(d, 1.0))
        push = jnp.where(active, r, 0.0)
        # isolated vertices (deg 0) absorb their whole mass into p
        p = p + jnp.where(d > 0, alpha * push, push)
        give = jnp.where(d > 0,
                         (1.0 - alpha) * push / jnp.maximum(d, 1.0), 0.0)
        r = jnp.where(active, 0.0, r)
        # neighbor contributions of the active entries ([S, cap, W] gather;
        # adjacency pad and inactive lanes park on the id sentinel n)
        nbrs = jnp.take(adj, jnp.minimum(idx, n - 1), axis=0)
        live = active[:, :, None] & (nbrs < n)
        cand_id = jnp.where(live, nbrs, n).reshape(s_batch, cap * width)
        cand_r = jnp.where(live, give[:, :, None],
                           0.0).reshape(s_batch, cap * width)
        # sort-merge: table ∪ candidates by id, compact duplicate ids into
        # the segment head's slot via rank = cumsum(head) - 1
        all_id = jnp.concatenate([idx, cand_id], axis=1)
        all_p = jnp.concatenate([p, jnp.zeros_like(cand_r)], axis=1)
        all_r = jnp.concatenate([r, cand_r], axis=1)
        perm = jnp.argsort(all_id, axis=1, stable=True)
        sid = jnp.take_along_axis(all_id, perm, axis=1)
        sp = jnp.take_along_axis(all_p, perm, axis=1)
        sr = jnp.take_along_axis(all_r, perm, axis=1)
        svalid = sid < n
        head = svalid & jnp.concatenate(
            [jnp.ones((s_batch, 1), bool), sid[:, 1:] != sid[:, :-1]], axis=1)
        rank = jnp.cumsum(head, axis=1) - 1
        ovf = ovf | jnp.any(jnp.sum(head, axis=1) > cap)
        rank = jnp.where(svalid, rank, cap)           # sentinels drop below
        new_idx = jnp.full((s_batch, cap), n, jnp.int32).at[
            rows, rank].min(sid, mode="drop")
        new_p = jnp.zeros((s_batch, cap), jnp.float32).at[
            rows, rank].add(sp, mode="drop")
        new_r = jnp.zeros((s_batch, cap), jnp.float32).at[
            rows, rank].add(sr, mode="drop")
        return new_idx, new_p, new_r, it + 1, ovf

    def cond(state):
        idx, _, r, it, ovf = state
        d = entry_deg(idx)
        any_active = jnp.any((idx < n) & (r >= eps * jnp.maximum(d, 1.0)))
        return any_active & (it < max_iters) & ~ovf

    return jax.lax.while_loop(
        cond, body, (idx0, p0, r0, jnp.int32(0), jnp.bool_(False)))


def ppr_push_sparse(graph: Graph, seeds: jax.Array, alpha: float = 0.15,
                    eps: float = 1e-4, max_iters: int = 200,
                    frontier_cap: Optional[int] = None) -> SparseFrontier:
    """Sparse-frontier ACL push: same dynamics as :func:`ppr_push`, memory
    ``O(S · cap)`` with ``cap = O(1/(alpha·eps))`` instead of ``O(S · n)``.

    Args:
      graph:        frozen Graph or streaming view; reads ``deg``/``adj``.
      seeds:        int32[S] seed vertex ids (pow2-padded internally).
      alpha, eps:   ACL parameters (traced scalars — no retrace per value).
      max_iters:    hard cap on synchronous push rounds.
      frontier_cap: capacity override; ``None`` sizes from the ACL bound
                    (see :func:`frontier_cap_for`).

    Returns:
      A :class:`SparseFrontier`. Check ``overflowed`` before consuming: a
      True flag means the cap was exceeded mid-round and the buffers are
      truncated — callers must fall back to the dense push (spill).
    """
    seeds = jnp.asarray(seeds, jnp.int32).reshape(-1)
    seeds_b, s = _padded_seeds(seeds)
    cap = frontier_cap_for(alpha, eps, graph.n, frontier_cap)
    with trace.span("ppr.push", mode="sparse", n=int(graph.n), cap=int(cap),
                    seeds=int(s)) as sp:
        idx, p, r, iters, ovf = _ppr_push_sparse_impl(
            graph.deg, graph.adj, seeds_b, jnp.float32(alpha),
            jnp.float32(eps), n=graph.n, cap=cap, max_iters=max_iters)
        fr = SparseFrontier(idx=idx[:s], p=p[:s], r=r[:s], iterations=iters,
                            overflowed=ovf, n=graph.n)
        size = int(fr.sizes().max()) if s else 0
        sp.set(frontier_size=size, spilled=bool(fr.overflowed))
        obs_metrics.REGISTRY.histogram("ppr.frontier_size").observe(size)
    return fr


def ppr_power_iteration(graph: Graph, seeds: jax.Array, alpha: float = 0.15,
                        iters: int = 200) -> jax.Array:
    """Dense power-iteration PPR reference: ``p ← α·e_s + (1−α)·A D⁻¹ p``.

    The fixed point this converges to is exactly what :func:`ppr_push`
    approximates (same teleport convention), so it serves as the test oracle.
    Returns float32[S, n].
    """
    n = graph.n
    deg = graph.deg.astype(jnp.float32)
    edges = graph.edges
    seeds = jnp.asarray(seeds, jnp.int32).reshape(-1)
    s_batch = seeds.shape[0]
    e_s = jnp.zeros((s_batch, n), jnp.float32).at[
        jnp.arange(s_batch), seeds].add(1.0)

    def step(p, _):
        give = jnp.where(deg[None, :] > 0, p / jnp.maximum(deg[None, :], 1.0),
                         0.0)
        recv = jnp.zeros_like(p)
        recv = recv.at[:, edges[:, 1]].add(give[:, edges[:, 0]])
        recv = recv.at[:, edges[:, 0]].add(give[:, edges[:, 1]])
        # deg-0 vertices hold their mass (matches push's absorb-to-p)
        hold = jnp.where(deg[None, :] > 0, 0.0, p)
        return alpha * e_s + (1.0 - alpha) * (recv + hold), None

    p, _ = jax.lax.scan(step, e_s, None, length=iters)
    return p


# ----------------------------------------------------------------------------
# phase 2: sweep cut with sketch-gated cut increments
# ----------------------------------------------------------------------------

def _vertex_bloom_rows(order: jax.Array, n: int, words: int, num_hashes: int,
                       seed: int) -> jax.Array:
    """uint32[S, k, words]: single-vertex Bloom rows for the sweep order.

    Built through the one shared builder (``sketches.bloom_rows`` on
    ``[S·k, 1]`` pseudo-adjacency rows; the sweep-pad sentinel ``n`` is
    exactly the builder's pad value), so the prefix filter *provably* uses
    the same hash family and bit layout as the neighborhood sketch — the
    property the AND/OR estimators depend on.
    """
    s_batch, k = order.shape
    rows = bloom_rows(order.reshape(-1, 1), n=n, words=words,
                      num_hashes=num_hashes, seed=seed)
    return rows.reshape(s_batch, k, words)


def _prefix_intersections(deg: jax.Array, adj: jax.Array, n: int,
                          order: jax.Array, sketch: Optional[SketchSet],
                          plan: eng.EnginePlan) -> jax.Array:
    """float32[S, k]: |N(order_j) ∩ {order_0..order_{j-1}}| per sweep step.

    Sketch path (kind == "bf"): exclusive prefix-OR of single-vertex Bloom
    rows gives ``B(S_{j-1})``; one AND+popcount against the neighborhood row
    ``B(N(order_j))`` per step, through the compiled 2-way AND set
    expression in dense form (fused Pallas pass when ``plan.use_kernel``,
    jnp otherwise). Exact path: gather each swept vertex's padded
    adjacency row and count neighbors whose sweep rank is smaller.
    """
    s_batch, k = order.shape
    if sketch is not None and sketch.kind == "bf":
        words = sketch.data.shape[1]
        total_bits = words * 32
        elem = _vertex_bloom_rows(order, n, words, sketch.num_hashes,
                                  sketch.seed)
        prefix_inc = jax.lax.associative_scan(jnp.bitwise_or, elem, axis=1)
        prefix = jnp.concatenate(
            [jnp.zeros((s_batch, 1, words), jnp.uint32),
             prefix_inc[:, :-1]], axis=1)                    # exclusive
        safe = jnp.where(order < n, order, 0)
        nbr_rows = jnp.take(sketch.data, safe, axis=0)       # [S, k, words]
        # inclusion–exclusion (the paper's OR estimator): both set sizes are
        # *known exactly* here — |N(v_j)| = d(v_j) and |S_{j-1}| = j — so only
        # the union size needs estimating. Unlike the AND form this stays
        # accurate while the prefix filter fills up: it saturates with the
        # union's fill fraction, which core.bounds.sweep_cut_rmse models.
        from ...engine import setexpr
        u_row, v_row = setexpr.rows(2)
        ce = setexpr.compile_expr(u_row & v_row, block_w=plan.block_w,
                                  use_kernel=plan.use_kernel)
        ones_and = ce.ones_rows(
            nbr_rows.reshape(-1, words),
            prefix.reshape(-1, words)).reshape(s_batch, k)
        ones_nbr = jnp.sum(jax.lax.population_count(nbr_rows), axis=-1)
        ones_pre = jnp.sum(jax.lax.population_count(prefix), axis=-1)
        ones_or = ones_nbr + ones_pre - ones_and
        union_est = bf_intersection_and_from_ones(ones_or, total_bits,
                                                  sketch.num_hashes)
        d_j = jnp.take(deg, safe).astype(jnp.float32)
        psize = jnp.arange(k, dtype=jnp.float32)[None, :]    # |S_{j-1}| = j
        est = d_j + psize - union_est
        # an intersection is bounded by the smaller of the two true sets
        return jnp.clip(est, 0.0, jnp.minimum(d_j, psize))

    # exact fallback: rank-compare through the padded adjacency
    rank = jnp.full((s_batch, n + 1), k, jnp.int32)
    rank = rank.at[jnp.arange(s_batch)[:, None],
                   jnp.minimum(order, n)].set(
        jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (s_batch, k)))
    rank = rank.at[:, n].set(k)                    # adjacency pad sentinel
    nbrs = jnp.take(adj, jnp.where(order < n, order, 0),
                    axis=0)                                  # [S, k, cap]
    nbr_rank = jnp.take_along_axis(
        rank, nbrs.reshape(s_batch, -1), axis=1).reshape(nbrs.shape)
    before = nbr_rank < jnp.arange(k, dtype=jnp.int32)[None, :, None]
    valid = nbrs < n
    return jnp.sum(before & valid, axis=-1).astype(jnp.float32)


def _sweep_scan(deg: jax.Array, adj: jax.Array, order: jax.Array,
                in_sweep: jax.Array, vol_total: jax.Array,
                sketch: Optional[SketchSet], plan: eng.EnginePlan, *, n: int):
    """Conductance scan over an already-derived sweep order.

    Shared verbatim by the dense and sparse sweep entries: given the same
    ``(order, in_sweep)`` it reads only ``deg``/``adj``/``vol_total``, so the
    two paths' conductance profiles are bit-identical whenever their orders
    agree (invariant 10 — the frontier layout may perturb PPR values in the
    last ulp, but never the profile arithmetic downstream of the order).
    """
    support = jnp.sum(in_sweep, axis=1).astype(jnp.int32)
    d_j = jnp.where(in_sweep, jnp.take(deg, jnp.minimum(order, n - 1)), 0.0)
    inter = jnp.where(
        in_sweep,
        _prefix_intersections(deg, adj, n, order, sketch, plan), 0.0)
    vol = jnp.cumsum(d_j, axis=1)
    cut = jnp.cumsum(d_j - 2.0 * inter, axis=1)
    cut = jnp.maximum(cut, 0.0)                # sketch noise can dip below 0
    vol_rest = vol_total - vol
    denom = jnp.minimum(vol, vol_rest)
    ok = in_sweep & (denom > 0.0)
    conductance = jnp.where(ok, cut / jnp.maximum(denom, 1.0), jnp.inf)
    return order, conductance, support


@functools.partial(jax.jit, static_argnames=("n", "plan"))
def _sweep_cut_impl(deg: jax.Array, adj: jax.Array, ppr: jax.Array,
                    vol_total: jax.Array, sketch: Optional[SketchSet],
                    plan: eng.EnginePlan, *, n: int):
    """Jitted dense sweep over raw arrays; ``vol_total`` (= 2m) arrives as a
    traced scalar so a streaming delta's changed edge count does not retrace
    (the Graph pytree's static ``n_edges`` would). ``top_k`` breaks score
    ties by smallest vertex id — the sparse entry matches this exactly."""
    deg = deg.astype(jnp.float32)
    score = ppr / jnp.maximum(deg[None, :], 1.0)
    k = max(1, min(int(plan.sweep_cap), n))
    top_score, order = jax.lax.top_k(score, k)
    in_sweep = top_score > 0.0                               # [S, k]
    order = jnp.where(in_sweep, order, n).astype(jnp.int32)  # pad -> sentinel
    return _sweep_scan(deg, adj, order, in_sweep, vol_total, sketch, plan,
                       n=n)


@functools.partial(jax.jit, static_argnames=("n", "plan"))
def _sweep_cut_sparse_impl(deg: jax.Array, adj: jax.Array, idx: jax.Array,
                           pval: jax.Array, vol_total: jax.Array,
                           sketch: Optional[SketchSet],
                           plan: eng.EnginePlan, *, n: int):
    """Jitted sparse sweep: derive the order from the ``[S, cap]`` support
    table instead of a dense ``[S, n]`` score tensor. The table is ascending
    by vertex id, so ``top_k`` over slots breaks score ties by smallest id —
    the same tie order the dense entry produces — and the shared scan then
    yields bit-identical conductance profiles on agreeing orders."""
    deg = deg.astype(jnp.float32)
    cap = idx.shape[1]
    valid = idx < n
    d = jnp.where(valid, jnp.take(deg, jnp.minimum(idx, n - 1)), 1.0)
    # invalid slots score -1 so they sort after every real (≥ 0) score
    score = jnp.where(valid, pval / jnp.maximum(d, 1.0), -1.0)
    k = max(1, min(int(plan.sweep_cap), cap, n))
    top_score, pos = jax.lax.top_k(score, k)
    order = jnp.take_along_axis(idx, pos, axis=1)
    in_sweep = top_score > 0.0                               # [S, k]
    order = jnp.where(in_sweep, order, n).astype(jnp.int32)  # pad -> sentinel
    return _sweep_scan(deg, adj, order, in_sweep, vol_total, sketch, plan,
                       n=n)


def sweep_cut(graph: Graph, ppr, sketch: Optional[SketchSet] = None,
              plan: Optional[eng.EnginePlan] = None):
    """Batched sweep-cut conductance scan over degree-normalized PPR mass.

    Args:
      graph:  the graph the PPR vectors live on.
      ppr:    float32[S, n] PPR estimates (from :func:`ppr_push`) or a
              :class:`SparseFrontier` (from :func:`ppr_push_sparse`) — the
              sparse form sweeps the support table directly and never
              materializes an ``[S, n]`` tensor.
      sketch: optional SketchSet; a Bloom sketch routes the cut increments
              through prefix-filter AND+popcounts, anything else (or None)
              uses the exact rank-compare fallback.
      plan:   EnginePlan; ``plan.sweep_cap`` bounds the swept prefix length
              and ``plan.use_kernel`` routes Bloom popcounts through the
              Pallas pair kernel.

    Returns:
      ``(order, conductance, support)`` — int32[S, k] sweep order,
      float32[S, k] per-prefix conductance (inf at invalid prefixes), and
      int32[S] number of positive-mass vertices swept.
    """
    plan = plan if plan is not None else eng.plan_for(graph, sketch)
    if isinstance(ppr, SparseFrontier):
        return _sweep_cut_sparse_impl(graph.deg, graph.adj, ppr.idx, ppr.p,
                                      jnp.float32(2.0 * graph.m), sketch,
                                      plan, n=graph.n)
    return _sweep_cut_impl(graph.deg, graph.adj, ppr,
                           jnp.float32(2.0 * graph.m), sketch, plan,
                           n=graph.n)


def local_cluster(graph: Graph, seeds, alpha: float = 0.15, eps: float = 1e-4,
                  sketch: Optional[SketchSet] = None,
                  plan: Optional[eng.EnginePlan] = None,
                  max_iters: int = 200, **kw) -> LocalClusterResult:
    """Seed-centric local clustering: PPR push then a sweep-cut scan.

    Args:
      graph:  frozen Graph or a streaming ``DynamicGraph.view()``.
      seeds:  int32[S] (or scalar) seed vertex ids.
      alpha:  PPR teleport probability.
      eps:    push tolerance (smaller = larger support, better clusters).
      sketch: optional SketchSet for sketch-gated cut increments ("bf" kind
              engages the prefix-filter path; others fall back to exact).
      plan:   EnginePlan or legacy kwargs (``sweep_cap=``, ``use_kernel=``,
              ``frontier_mode=``, ``frontier_cap=``).
      max_iters: push round cap.

    Returns:
      A :class:`LocalClusterResult` with per-seed sweep order, conductance
      profile, and the best (minimum-conductance) prefix. The push frontier
      layout follows ``plan.frontier_mode``; a sparse-path overflow spills
      to the dense push transparently (``result.spilled`` records it, the
      ``ppr.spill`` counter counts it — slower, never wrong).
    """
    plan = eng.resolve_plan(plan, graph, sketch, kw)
    seeds = jnp.asarray(seeds, jnp.int32).reshape(-1)
    mode = resolve_frontier_mode(plan, graph.n, graph.d_max, alpha, eps)
    frontier = None
    spilled = False
    if mode == "sparse":
        fr = ppr_push_sparse(graph, seeds, alpha, eps, max_iters,
                             plan.frontier_cap)
        if bool(fr.overflowed):
            # spill: the cap was exceeded mid-round, the buffers are
            # truncated — recompute densely (perf event, never correctness)
            spilled = True
            obs_metrics.REGISTRY.counter("ppr.spill").inc()
        else:
            frontier = fr
    if frontier is not None:
        p = r = None
        iters = frontier.iterations
        order, conductance, support = sweep_cut(graph, frontier, sketch, plan)
    else:
        p, r, iters = ppr_push(graph, seeds, alpha, eps, max_iters)
        order, conductance, support = sweep_cut(graph, p, sketch, plan)
    best_idx = jnp.argmin(conductance, axis=1).astype(jnp.int32)
    best_phi = jnp.take_along_axis(conductance, best_idx[:, None],
                                   axis=1)[:, 0]
    # an all-inf profile (isolated seed, no valid prefix) has no cluster:
    # report size 0 rather than a bogus 1-element prefix of sentinel ids
    best_size = jnp.where(jnp.isfinite(best_phi), best_idx + 1, 0)
    return LocalClusterResult(
        order=order, conductance=conductance, best_idx=best_idx,
        best_conductance=best_phi,
        best_size=best_size, support=support, ppr=p, residual=r,
        iterations=iters, frontier=frontier, spilled=spilled)
