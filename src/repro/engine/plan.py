"""Execution planning for the batched mining engine.

An :class:`EnginePlan` is the single description of *how* set-intersection
work is executed — edge batching/padding, Pallas block shapes, sketch
estimator selection, degree-ordered edge layout, and optional edge-axis
sharding. Every algorithm consumes one instead of carrying its own chunk
plumbing (the GBBS "shared parallel primitives" discipline applied to the
ProbGraph hot loop).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.graph import Graph
from ..core.sketches import SketchSet
from ..obs import trace


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """Static execution parameters shared by all mining algorithms.

    Attributes:
      edge_chunk:   edges per scan-fold step (HBM working-set knob).
      block_e:      tuples per grid step of the fused Pallas popcount pass.
      block_w:      sketch words per grid step of the fused pass.
      use_kernel:   route BF popcounts through the fused Pallas pass.
      degree_order: sort edge blocks by hub endpoint so high-degree rows are
                    revisited by consecutive blocks (VMEM/HBM-stream reuse).
      estimator:    estimator override (e.g. "bf_l" on a "bf" sketch).
      variant:      1-Hash Jaccard variant ("union" | "naive").
      shard_edges:  shard_map the edge fold over the active mesh's edge axis
                    (see repro.distributed.sharding; no-op without a mesh).
      sweep_cap:    max swept prefix length for local clustering sweep cuts
                    (bounds the per-seed sweep tensor shapes).
      frontier_mode: PPR push frontier layout — "dense" keeps the classic
                    ``[S, n]`` residual tensors, "sparse" stores per-seed
                    support in capped ``[S, cap]`` index+value buffers, and
                    "auto" (default) picks sparse only when the cap implied
                    by ``1/(alpha·eps)``, times the adjacency rows each slot
                    gathers, is far enough below ``n`` to pay.
      frontier_cap: explicit sparse-frontier capacity override (entries per
                    seed; pow2-bucketed). ``None`` sizes it from the ACL
                    support bound ``O(1/(alpha·eps))``. Undersizing is safe:
                    overflow spills to the dense push (slower, never wrong).
    """

    edge_chunk: int = 65536
    block_e: int = 256
    block_w: int = 512
    use_kernel: bool = False
    degree_order: bool = False
    estimator: Optional[str] = None
    variant: str = "union"
    shard_edges: bool = False
    sweep_cap: int = 512
    frontier_mode: str = "auto"
    frontier_cap: Optional[int] = None

    def with_(self, **overrides) -> "EnginePlan":
        """Return a copy of this plan with the given fields replaced."""
        return dataclasses.replace(self, **overrides)


def pow2_bucket(size: int, lo: int = 1) -> int:
    """Next power of two ≥ max(size, lo).

    The fixed batch shapes that bound XLA recompiles under varying-size work:
    session cache refresh, streaming sketch inserts/rebuilds, and query-server
    batches all pad to these buckets.
    """
    return max(lo, 1 << (max(int(size), 1) - 1).bit_length())


def plan_for(graph: Graph, sketch: Optional[SketchSet] = None,
             **overrides) -> EnginePlan:
    """Heuristic default plan for a (graph, sketch) pair.

    Chunk size is clamped so a chunk's gathered sketch rows stay well under
    VMEM-scale working sets; degree ordering is enabled on the kernel path
    where block locality pays for the one-time sort.
    """
    with trace.span("engine.plan_for", n=int(graph.n), m=int(graph.m),
                    kind=sketch.kind if sketch is not None else "exact"):
        words = (sketch.data.shape[1]
                 if sketch is not None and sketch.kind == "bf" else 64)
        target_words = 1 << 22              # ~16 MiB of gathered uint32 rows
        chunk = max(1024, min(65536, target_words // max(words, 1)))
        base = EnginePlan(edge_chunk=int(chunk),
                          degree_order=bool(overrides.get("use_kernel",
                                                          False)))
        return base.with_(**overrides)


# ----------------------------------------------------------------------------
# edge layout: degree-bucketed ordering for hub-row residency
# ----------------------------------------------------------------------------

def order_edges_by_hub(graph: Graph, edges: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Reorder edges so blocks revisit hub rows consecutively.

    Sort key is (hub degree bucket desc, hub id): edges sharing their
    highest-degree endpoint become adjacent, so consecutive (block_e, block_w)
    gather steps re-read the same sketch row while it is hot. Returns
    (edges_sorted, inv) with ``values_sorted[inv] == values_original_order``.
    """
    du = jnp.take(graph.deg, edges[:, 0])
    dv = jnp.take(graph.deg, edges[:, 1])
    hub = jnp.where(du >= dv, edges[:, 0], edges[:, 1])
    hub_deg = jnp.maximum(du, dv).astype(jnp.int32)
    # bucket = floor(log2(deg)) + 1, via the float exponent; descending so
    # hub-heavy blocks lead the schedule
    bucket = jnp.frexp(jnp.maximum(hub_deg, 1).astype(jnp.float32))[1]
    perm = jnp.lexsort((hub, -bucket))
    inv = jnp.argsort(perm)
    return jnp.take(edges, perm, axis=0), inv


# ----------------------------------------------------------------------------
# shared chunked fold / map over edge-like index arrays
# ----------------------------------------------------------------------------

def _pad_edges(edges: jax.Array, chunk: int):
    m = edges.shape[0]
    pad = (-m) % chunk
    edges_p = jnp.concatenate(
        [edges, jnp.zeros((pad, edges.shape[1]), edges.dtype)], axis=0)
    mask = jnp.concatenate([jnp.ones(m, bool), jnp.zeros(pad, bool)])
    return edges_p, mask


def fold_edges_masked(edges: jax.Array, mask: jax.Array, chunk_fn,
                      plan: EnginePlan,
                      vary_axes: Tuple[str, ...] = ()) -> jax.Array:
    """Scan-fold of ``chunk_fn(pairs, mask) -> scalar`` with a caller-supplied
    validity mask; ``edges`` must already be chunk-padded when chunked.

    Inside ``shard_map`` the chunk sums vary over the mesh axes the edges
    are split on; ``vary_axes`` names them so the scan carry starts out
    varying over the same axes.
    """
    m = edges.shape[0]
    if m == 0:
        return jnp.float32(0)
    if m <= plan.edge_chunk:
        return chunk_fn(edges, mask)

    def body(c, xs):
        """Scan step: accumulate one chunk's masked partial sum."""
        pairs, msk = xs
        return c + chunk_fn(pairs, msk), None

    init = jnp.float32(0)
    if vary_axes:
        init = jax.lax.pcast(init, vary_axes, to="varying")
    total, _ = jax.lax.scan(
        body, init,
        (edges.reshape(-1, plan.edge_chunk, edges.shape[1]),
         mask.reshape(-1, plan.edge_chunk)))
    return total


def fold_edges(edges: jax.Array, chunk_fn, plan: EnginePlan) -> jax.Array:
    """Masked scan-fold of ``chunk_fn(pairs, mask) -> scalar`` over chunks."""
    m = edges.shape[0]
    if m == 0:
        return jnp.float32(0)
    if m <= plan.edge_chunk:
        return chunk_fn(edges, jnp.ones(m, bool))
    edges_p, mask = _pad_edges(edges, plan.edge_chunk)
    return fold_edges_masked(edges_p, mask, chunk_fn, plan)


def map_edges(edges: jax.Array, chunk_fn, plan: EnginePlan) -> jax.Array:
    """Chunked map of ``chunk_fn(pairs) -> [C]`` over edges; returns [m]."""
    m = edges.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.float32)
    if m <= plan.edge_chunk:
        return chunk_fn(edges)
    edges_p, _ = _pad_edges(edges, plan.edge_chunk)
    out = jax.lax.map(chunk_fn,
                      edges_p.reshape(-1, plan.edge_chunk, edges.shape[1]))
    return out.reshape(-1)[:m]
