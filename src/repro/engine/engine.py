"""Batched mining engine: one execution seam for every ProbGraph algorithm.

Responsibilities (SISA's set-centric batching + GBBS's shared primitives):

  * ``pair_cardinality_fn``  — the |N_u ∩ N_v| provider, plan-dispatched
    between the exact galloping baseline, jnp estimator paths, and the
    fused Pallas popcount pass.
  * ``edge_cardinalities`` / ``sum_edge_cardinalities`` — chunked per-edge
    map / fold over an edge list with degree-ordered layout and optional
    shard_map over the edge axis (repro.distributed.sharding rules).
  * ``tuple_cardinality_ones`` / ``triple_cardinality_ones`` — the k-way
    popcount provider over row-index tuples, compiled from the k-way AND
    set expression (``repro.engine.setexpr``) to one fused Pallas pass
    over gathered rows or the equivalent jnp gather (bit-identical
    popcounts).
  * ``session`` — multi-query amortization: build the sketch once, run
    TC + LCC + clustering over the shared sketch and the shared per-edge
    cardinality pass, and 4-cliques over a cached triangle list.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from ..core.graph import Graph
from ..core.intersect import CardFn, make_pair_cardinality_fn
from ..core.sketches import SketchSet, build as build_sketch
from ..distributed import sharding
from ..obs import trace
from . import setexpr
from .plan import (EnginePlan, fold_edges, fold_edges_masked, map_edges,
                   order_edges_by_hub, plan_for, pow2_bucket)

_PLAN_KWARGS = ("edge_chunk", "block_e", "block_w", "use_kernel",
                "degree_order", "estimator", "variant", "shard_edges",
                "sweep_cap", "frontier_mode", "frontier_cap")


def resolve_plan(plan: Optional[EnginePlan], graph: Graph,
                 sketch: Optional[SketchSet] = None, kw: Optional[dict] = None
                 ) -> EnginePlan:
    """Merge legacy per-call kwargs (edge_chunk=, use_kernel=, ...) into a
    plan; keeps the pre-engine algorithm signatures working unchanged."""
    kw = kw or {}
    unknown = set(kw) - set(_PLAN_KWARGS)
    if unknown:
        raise TypeError(f"unknown plan option(s): {sorted(unknown)}")
    if plan is None:
        return plan_for(graph, sketch, **kw)
    return plan.with_(**kw) if kw else plan


def pair_cardinality_fn(graph: Graph, sketch: Optional[SketchSet],
                        plan: EnginePlan) -> CardFn:
    """The single |N_u ∩ N_v| seam, dispatched by the plan."""
    return make_pair_cardinality_fn(
        graph, sketch, use_kernel=plan.use_kernel, variant=plan.variant,
        estimator=plan.estimator, block_e=plan.block_e, block_w=plan.block_w)


def edge_cardinalities(graph: Graph, sketch: Optional[SketchSet],
                       plan: EnginePlan, edges: Optional[jax.Array] = None
                       ) -> jax.Array:
    """Per-edge |N_u ∩ N_v| (float32[m]) in the caller's edge order.

    Degree-ordered layout is applied internally (and inverted on the way
    out) so the kernel path sees hub-clustered blocks.
    """
    fn = pair_cardinality_fn(graph, sketch, plan)
    edges = graph.edges if edges is None else edges
    mapper = _sharded_map if plan.shard_edges else map_edges
    if plan.degree_order and edges.shape[0] > 1:
        edges_s, inv = order_edges_by_hub(graph, edges)
        return jnp.take(mapper(edges_s, fn, plan), inv)
    return mapper(edges, fn, plan)


def sum_edge_cardinalities(graph: Graph, sketch: Optional[SketchSet],
                           plan: EnginePlan,
                           card_fn: Optional[CardFn] = None) -> jax.Array:
    """Σ_{(u,v)∈E} |N_u ∩ N_v| — the TC numerator, fold-executed."""
    fn = card_fn or pair_cardinality_fn(graph, sketch, plan)
    edges = graph.edges
    if plan.degree_order and edges.shape[0] > 1:
        edges, _ = order_edges_by_hub(graph, edges)   # sums need no unsort

    def chunk(pairs, mask):
        """Masked partial sum of one edge chunk's cardinalities."""
        return jnp.sum(jnp.where(mask, fn(pairs), 0.0))

    if plan.shard_edges:
        return _sharded_fold(edges, chunk, plan)
    return fold_edges(edges, chunk, plan)


def _edge_shards(edges: jax.Array, plan: EnginePlan):
    """Split the edge axis over the active mesh's edge axes.

    Returns ``(mesh, axes, edges_p, mask)`` with the edge list zero-padded
    so every shard holds whole ``edge_chunk`` chunks, or None when no mesh
    is active or the rules map "edge" to no mesh axis.
    """
    mesh = sharding.active_mesh()
    if mesh is None:
        return None
    # the split is internal: callers pass and get back arrays placed
    # anywhere, so the shard_map runs with Auto axes even on a mesh whose
    # axes are Explicit (their sharded types would leak into single-device
    # consumers of the per-edge output, such as the LCC scatter)
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    axes = sharding.spec_for(("edge",), mesh=mesh)[0]
    if axes is None:
        return None
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    nshards = int(np.prod([mesh.shape[a] for a in axes]))
    m = edges.shape[0]
    pad = (-m) % (nshards * min(plan.edge_chunk, max(m, 1)))
    edges_p = jnp.concatenate(
        [edges, jnp.zeros((pad, edges.shape[1]), edges.dtype)], axis=0)
    mask = jnp.concatenate([jnp.ones(m, bool), jnp.zeros(pad, bool)])
    return mesh, axes, edges_p, mask


def _sharded_fold(edges: jax.Array, chunk_fn, plan: EnginePlan) -> jax.Array:
    """shard_map the masked edge fold over the active mesh's edge axes.

    Falls back to the local fold when no mesh is active. Fixed-size sketch
    rows mean every shard does identical work — the paper's no-straggler
    property — so a plain psum closes the reduction.
    """
    shards = _edge_shards(edges, plan)
    if shards is None:
        return fold_edges(edges, chunk_fn, plan)
    mesh, axes, edges_p, mask = shards

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(axes, None),
                                                          P(axes)),
                       out_specs=P())
    def fold_shard(edge_shard, mask_shard):
        """Per-shard fold, psum-reduced over the edge axes."""
        local = fold_edges_masked(edge_shard, mask_shard, chunk_fn, plan,
                                  vary_axes=axes)
        return jax.lax.psum(local, axes)

    return fold_shard(edges_p, mask)


def _sharded_map(edges: jax.Array, fn, plan: EnginePlan) -> jax.Array:
    """shard_map the chunked per-edge map over the active mesh's edge axes;
    the result stays split over the edge shards (local map without a
    mesh)."""
    shards = _edge_shards(edges, plan)
    if shards is None:
        return map_edges(edges, fn, plan)
    mesh, axes, edges_p, _ = shards
    out = jax.shard_map(lambda e: map_edges(e, fn, plan), mesh=mesh,
                        in_specs=P(axes, None), out_specs=P(axes))(edges_p)
    return out[:edges.shape[0]]


def tuple_cardinality_ones(sketch: SketchSet, tuples: jax.Array,
                           plan: EnginePlan) -> jax.Array:
    """popcnt(AND of the k referenced rows) per tuple — int32[T].

    The plan-dispatched face of the set-expression compiler for the common
    k-way AND: ``tuples`` is int32[T, k] and the cached compiled expression
    lowers to one fused Pallas pass (``plan.use_kernel``) or the
    equivalent jnp gather. Both produce identical popcounts, so downstream
    estimates are bit-identical.
    """
    if sketch.kind != "bf":
        raise ValueError("tuple_cardinality_ones needs a Bloom sketch")
    k = tuples.shape[1]
    ce = setexpr.compile_expr(setexpr.and_all(*setexpr.rows(k)),
                              block_e=plan.block_e, block_w=plan.block_w,
                              use_kernel=plan.use_kernel)
    return ce.ones(sketch.data, tuples)


def triple_cardinality_ones(sketch: SketchSet, triples: jax.Array,
                            plan: EnginePlan) -> jax.Array:
    """popcnt(Bu & Bv & Bw) per (u, v, w) triple — int32[T].

    The k=3 case of :func:`tuple_cardinality_ones` (kept as the named
    4-clique seam).
    """
    return tuple_cardinality_ones(sketch, triples, plan)


def wedge_quad_ones(sketch: SketchSet, u: jax.Array, v: jax.Array,
                    w_grid: jax.Array, x_grid: jax.Array,
                    plan: EnginePlan) -> jax.Array:
    """popcnt(Bu & Bv & Bw & Bx) over a wedge-pair grid: u, v int32[C],
    w int32[C, dw], x int32[C, dx] -> int32[C, dw, dx] (the 5-clique 4-way
    intersection provider).

    Kernel path flattens to (u, v, w, x) quads for the compiled 4-way AND
    expression — the workload that needed no new hand-rolled kernel; the
    jnp path keeps the broadcast form so u/v rows are gathered once per
    edge. Identical integer popcounts either way.
    """
    c, dw = w_grid.shape
    dx = x_grid.shape[1]
    if plan.use_kernel:
        quads = jnp.stack([
            jnp.broadcast_to(u[:, None, None], (c, dw, dx)).reshape(-1),
            jnp.broadcast_to(v[:, None, None], (c, dw, dx)).reshape(-1),
            jnp.broadcast_to(w_grid[:, :, None], (c, dw, dx)).reshape(-1),
            jnp.broadcast_to(x_grid[:, None, :], (c, dw, dx)).reshape(-1),
        ], axis=1)
        return tuple_cardinality_ones(sketch, quads, plan).reshape(c, dw, dx)
    ru = jnp.take(sketch.data, u, axis=0)[:, None, None, :]
    rv = jnp.take(sketch.data, v, axis=0)[:, None, None, :]
    rw = jnp.take(sketch.data, w_grid, axis=0)[:, :, None, :]
    rx = jnp.take(sketch.data, x_grid, axis=0)[:, None, :, :]
    return jnp.sum(jax.lax.population_count(ru & rv & rw & rx), axis=-1
                   ).astype(jnp.int32)


# ----------------------------------------------------------------------------
# answer footprints (the serving tier's invalidation unit)
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Footprint:
    """The vertex set one answer was computed from.

    ProbGraph's fixed-size sketch rows make answer provenance *precise*: a
    pair score reads exactly two sketch rows and two degrees, a membership
    test one row, a local cluster the rows/degrees of its PPR support — so
    ``vertices`` lists exactly the vertex ids whose adjacency, degree, or
    sketch row the answer depends on. A result cached above the engine stays
    valid until a delta touches (or a maintenance flush rebuilds) a footprint
    vertex; ``vertices is None`` marks whole-graph answers (triangle counts
    fold every edge) that no delta can survive.
    """

    vertices: Optional[np.ndarray]

    @classmethod
    def whole_graph(cls) -> "Footprint":
        """Footprint of an answer that reads every edge (e.g. TC)."""
        return cls(None)

    @classmethod
    def of(cls, *vertex_sets) -> "Footprint":
        """Union footprint of the given vertex id arrays / scalars."""
        arrs = [np.asarray(a, dtype=np.int64).reshape(-1)
                for a in vertex_sets if a is not None]
        arrs = [a for a in arrs if a.size]
        if not arrs:
            return cls(np.zeros(0, np.int64))
        return cls(np.unique(np.concatenate(arrs)))

    @property
    def is_whole_graph(self) -> bool:
        """True when the answer depends on the entire graph."""
        return self.vertices is None

    def intersects(self, vertices) -> bool:
        """Does any of ``vertices`` invalidate this footprint?"""
        if self.vertices is None:
            return True
        return bool(np.isin(np.asarray(vertices, dtype=np.int64),
                            self.vertices).any())


# ----------------------------------------------------------------------------
# multi-query session
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceCarry:
    """Device-resident carry for :meth:`MiningSession.refresh`.

    The host-array carry contract uploads O(m) indices per refresh; a
    device-resident streaming graph instead derives the position carry on
    device (from its edge-list splice) and uploads only the delta-sized
    recompute set, so refresh traffic scales with the delta.

    Attributes:
      carry:         int32[>= m_new] device — new edge j carried old position
                     ``carry[j]`` (>= 0), or < 0 for an inserted edge. Entries
                     in the recompute set may be stale; they are overwritten.
      recompute_pos: int32[R_b] device — positions whose cached cardinality
                     must be recomputed (covers every carry < 0 and every
                     edge with an invalidated endpoint), padded with >= m_new
                     (dropped by the scatter).
      n_recompute:   the true number R of recomputed positions.
      edges_full:    int32[E_cap, 2] device — the capacity-padded edge buffer
                     the recompute edges are gathered from. Its *stable*
                     shape keeps the gather's compiled program cached across
                     deltas (graph.edges is [m, 2] and m changes every
                     delta); rows at padded positions are sentinels whose
                     cardinalities the scatter drops.
    """

    carry: jax.Array
    recompute_pos: jax.Array
    n_recompute: int
    edges_full: jax.Array


@functools.partial(jax.jit, static_argnames=("m_new",))
def _carry_cards(old_cards, carry, *, m_new):
    c = jnp.clip(carry[:m_new], 0, old_cards.shape[0] - 1)
    return jnp.take(old_cards, c)


@functools.partial(jax.jit, static_argnames=("m_new",))
def _carry_scatter_cards(old_cards, carry, pos, sub, *, m_new):
    """One fused program per (m_old, m_new, R_b): slice-gather the carried
    cardinalities, overwrite the recomputed subset (padded pos >= m_new are
    dropped)."""
    c = jnp.clip(carry[:m_new], 0, old_cards.shape[0] - 1)
    return jnp.take(old_cards, c).at[pos].set(sub, mode="drop")


class MiningSession:
    """Amortizes one sketch build + one per-edge cardinality pass across
    TC, LCC and Jarvis-Patrick, and one triangle list across 4-clique
    queries, on the same graph."""

    def __init__(self, graph: Graph, sketch: Optional[SketchSet],
                 plan: EnginePlan):
        self.graph = graph
        self.sketch = sketch
        self.plan = plan
        self._edge_cards: Optional[jax.Array] = None
        self._triangles: Optional[tuple] = None

    def fork(self) -> "MiningSession":
        """Copy-on-write twin sharing this session's state by reference.

        Every field a session mutates (``graph``, ``sketch``,
        ``_edge_cards``, ``_triangles``) is only ever *rebound*, never
        edited in place, so a fork plus :meth:`refresh` builds the next
        version's session while the original keeps serving the old one
        untouched — the snapshot-isolation seam ``StreamSession``
        publishes through.
        """
        new = MiningSession(self.graph, self.sketch, self.plan)
        new._edge_cards = self._edge_cards
        new._triangles = self._triangles
        return new

    def edge_cardinalities(self) -> jax.Array:
        """Cached |N_u ∩ N_v| over graph.edges (the shared mining pass)."""
        if self._edge_cards is None:
            with trace.span("engine.edge_cards",
                            edges=int(self.graph.m)) as sp:
                self._edge_cards = sp.fence(edge_cardinalities(
                    self.graph, self.sketch, self.plan))
        return self._edge_cards

    def triangle_count(self) -> jax.Array:
        """Scalar TC estimate from the shared per-edge cardinality pass."""
        with trace.span("engine.triangle_count"):
            return jnp.sum(self.edge_cardinalities()) / 3.0

    def local_clustering(self) -> jax.Array:
        """Per-vertex clustering coefficients float32[n] (shared pass)."""
        from ..core.algorithms.tc import local_clustering_coefficient
        with trace.span("engine.local_clustering"):
            return local_clustering_coefficient(
                self.graph, self.sketch, plan=self.plan,
                edge_cards=self.edge_cardinalities())

    def jarvis_patrick(self, similarity: str = "common",
                       threshold: float = 2.0):
        """Jarvis–Patrick clustering ``(labels int32[n], num_clusters)``."""
        from ..core.algorithms.clustering import jarvis_patrick
        with trace.span("engine.jarvis_patrick", similarity=similarity):
            return jarvis_patrick(self.graph, self.sketch, similarity,
                                  threshold, plan=self.plan,
                                  edge_cards=self.edge_cardinalities())

    def triangles(self) -> tuple:
        """Cached triangle list ``(int32[T_cap, 3], T)``: each triangle once,
        a < b < c in (degree, id) rank order, zero rows past T
        (:func:`core.algorithms.cliques.triangle_list`)."""
        if self._triangles is None:
            from ..core.algorithms.cliques import triangle_list
            with trace.span("engine.triangles") as sp:
                tris, count, wedges = triangle_list(self.graph)
                sp.set(wedges=wedges, triangles=count,
                       capacity=int(tris.shape[0]))
                self._triangles = (sp.fence(tris), count)
        return self._triangles

    def four_clique_count(self, exact_closing_test: bool = True,
                          **kw):
        """Scalar 4-clique count estimate (3-way sketch intersections over
        the cached triangle list); ``return_ones=True`` also returns the
        exact Σ of the 3-way AND popcounts
        (:func:`core.algorithms.cliques.four_clique_count`)."""
        from ..core.algorithms.cliques import four_clique_count
        with trace.span("engine.four_clique_count") as sp:
            tris = self.triangles() if exact_closing_test else None
            return sp.fence(four_clique_count(
                self.graph, self.sketch, plan=self.plan,
                exact_closing_test=exact_closing_test, triangles=tris, **kw))

    def five_clique_count(self, **kw) -> jax.Array:
        """Scalar 5-clique count estimate (4-way sketch intersections)."""
        from ..core.algorithms.cliques import five_clique_count
        return five_clique_count(self.graph, self.sketch, plan=self.plan,
                                 **kw)

    def similarity(self, pairs: jax.Array, measure: str = "jaccard"
                   ) -> jax.Array:
        """Similarity scores float32[P] for vertex pairs int32[P, 2]."""
        from ..core.algorithms.similarity import pair_similarity
        return pair_similarity(self.graph, pairs, measure, self.sketch,
                               plan=self.plan)

    def local_cluster(self, seeds, alpha: float = 0.15, eps: float = 1e-4,
                      **kw):
        """Seed-centric local clustering (PPR push + sketch-gated sweep).

        Args:
          seeds: int32[S] (or scalar) seed vertex ids; the whole batch runs
                 as one vmapped push + sweep.
          alpha: PPR teleport probability.
          eps:   push tolerance (residual threshold per unit degree).
          **kw:  forwarded to :func:`core.algorithms.localcluster.local_cluster`
                 (e.g. ``max_iters=``, or plan overrides such as
                 ``frontier_mode=`` / ``frontier_cap=``).

        Returns:
          A :class:`~repro.core.algorithms.localcluster.LocalClusterResult`
          with per-seed sweep order, conductance profile and best prefix.
          The push frontier layout (dense ``[S, n]`` vs capped sparse
          ``[S, cap]``) follows the session plan's ``frontier_mode``.
        """
        from ..core.algorithms.localcluster import local_cluster
        with trace.span("engine.local_cluster", alpha=float(alpha),
                        eps=float(eps)) as sp:
            res = local_cluster(self.graph, seeds, alpha, eps, self.sketch,
                                plan=self.plan, **kw)
            sp.set(sparse=res.frontier is not None, spilled=bool(res.spilled))
            return res

    def edge_similarity(self, measure: str = "jaccard") -> jax.Array:
        """Similarity scores over graph.edges from the cached shared pass."""
        from ..core.algorithms.similarity import similarity_from_cardinalities
        edges = self.graph.edges
        du = jnp.take(self.graph.deg, edges[:, 0]).astype(jnp.float32)
        dv = jnp.take(self.graph.deg, edges[:, 1]).astype(jnp.float32)
        return similarity_from_cardinalities(self.edge_cardinalities(),
                                             du, dv, measure)

    def refresh(self, graph: Graph, sketch: Optional[SketchSet] = None,
                carry_index: Optional[np.ndarray] = None) -> Optional[int]:
        """Delta-aware cache invalidation: repoint the session at an updated
        (graph, sketch) and recompute only the invalidated edge cardinalities.

        ``carry_index[j]`` is the position of new edge j in the *previous*
        ``graph.edges`` when its cached cardinality is still valid (neither
        endpoint's neighborhood, degree, or sketch row changed), or -1 to
        recompute. With ``carry_index=None`` the whole cache is dropped.
        The triangle list is always dropped (rebuilt lazily).
        A :class:`DeviceCarry` keeps the whole exchange on device (carried
        values are gathered by the device permutation, only the delta-sized
        recompute positions were uploaded). Returns the number of per-edge
        cardinalities recomputed, or ``None`` when the cache was dropped
        instead (the full pass then happens lazily — nothing was carried
        over).

        Per-pair estimators are elementwise in the pair, so recomputing only
        the invalidated subset is bit-identical to a from-scratch pass.
        """
        with trace.span("engine.refresh") as sp:
            result = self._refresh(graph, sketch, carry_index)
            sp.set(recomputed=-1 if result is None else result)
            return result

    def _refresh(self, graph, sketch, carry_index):
        old_cards = self._edge_cards
        self.graph = graph
        self._triangles = None
        if sketch is not None:
            self.sketch = sketch
        if (old_cards is None or carry_index is None
                or int(old_cards.shape[0]) == 0):
            self._edge_cards = None
            return None
        if isinstance(carry_index, DeviceCarry):
            return self._refresh_device(old_cards, carry_index)
        carry = np.asarray(carry_index, dtype=np.int64)
        if carry.shape[0] == 0:
            self._edge_cards = jnp.zeros((0,), jnp.float32)
            return 0
        recompute = np.nonzero(carry < 0)[0]
        cards = jnp.take(old_cards, jnp.asarray(np.where(carry < 0, 0, carry)))
        if recompute.size:
            # pad the subset to a power-of-two bucket so repeated deltas of
            # varying size reuse one compiled cardinality program per bucket
            bucket = pow2_bucket(recompute.size)
            edges_np = np.asarray(graph.edges)
            sub_edges = np.zeros((bucket, 2), dtype=edges_np.dtype)
            sub_edges[:recompute.size] = edges_np[recompute]
            sub = edge_cardinalities(self.graph, self.sketch, self.plan,
                                     edges=jnp.asarray(sub_edges))
            cards = cards.at[jnp.asarray(recompute)].set(
                sub[:recompute.size])
        self._edge_cards = cards
        return int(recompute.size)

    def _refresh_device(self, old_cards: jax.Array, dc: DeviceCarry) -> int:
        """Device-side cache carry: gather by the splice permutation, then
        recompute only the invalidated positions (edges gathered on device,
        no host round-trip)."""
        m_new = self.graph.m
        if m_new == 0:
            self._edge_cards = jnp.zeros((0,), jnp.float32)
            return 0
        if dc.n_recompute:
            # gather from the stable-shape buffer so the compiled gather is
            # reused across deltas; padded positions hit sentinel rows whose
            # (garbage) cardinalities the fused scatter below drops. Clamp
            # the sentinel vertex id n to a real row first, so every path
            # gathers real sketch rows.
            sub_edges = jnp.minimum(
                jnp.take(dc.edges_full, dc.recompute_pos, axis=0),
                jnp.int32(max(self.graph.n - 1, 0)))
            sub = edge_cardinalities(self.graph, self.sketch, self.plan,
                                     edges=sub_edges)
            self._edge_cards = _carry_scatter_cards(
                old_cards, dc.carry, dc.recompute_pos, sub, m_new=m_new)
        else:
            self._edge_cards = _carry_cards(old_cards, dc.carry, m_new=m_new)
        return int(dc.n_recompute)

    def stats(self) -> dict:
        """Session facts: graph sizes, sketch kind/bytes, JSON-able plan."""
        sk = self.sketch
        return {
            "n": self.graph.n, "m": self.graph.m,
            "sketch": sk.kind if sk is not None else "exact",
            "sketch_bytes": int(sk.data.size * sk.data.dtype.itemsize)
            if sk is not None else 0,
            "plan": dataclasses.asdict(self.plan),
        }


def session(graph: Graph, sketch: Optional[SketchSet] | str = "bf",
            storage_budget: float = 0.25, num_hashes: int = 2, seed: int = 0,
            plan: Optional[EnginePlan] = None, **plan_kw) -> MiningSession:
    """Open a multi-query mining session over one shared sketch build.

    ``sketch`` may be a prebuilt SketchSet, a kind string ("bf" | "kh" |
    "1h" | "kmv") to build here, or None for the exact baseline.
    """
    with trace.span("engine.session"):
        if isinstance(sketch, str):
            sketch = build_sketch(graph, sketch, storage_budget,
                                  num_hashes=num_hashes, seed=seed)
        return MiningSession(graph, sketch, resolve_plan(plan, graph, sketch,
                                                         plan_kw))
