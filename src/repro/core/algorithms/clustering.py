"""Jarvis–Patrick clustering (paper Listing 4).

Two vertices u, v end up in the same cluster iff they are adjacent AND their
vertex similarity passes a threshold. Similarity ∈ {common (|N_u∩N_v| ≥ τ),
jaccard, overlap} — all driven by the |X∩Y| provider, exact or sketched.

Connected components over the kept edges run as data-parallel min-label
propagation (scatter-min + gather until fixpoint) — the shared-memory
union-find of the CPU implementation does not map to SPMD; label propagation
has depth O(diameter·log n) and is the standard XLA-friendly CC. Each round
costs a gather and a scatter per edge it visits, and only kept edges can
change a label, so the loop runs on a kept-first prefix of the edge list.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ... import engine as eng
from ...obs import trace
from ..graph import Graph
from ..sketches import SketchSet
from .similarity import similarity_from_cardinalities


@functools.partial(jax.jit, static_argnames=("n", "max_iters", "rows"))
def _connected_components(n: int, edges: jax.Array, keep: jax.Array,
                          max_iters: int = 200, rows: Optional[int] = None):
    """Min-label propagation over the kept edges, as one compiled program.

    Returns ``(labels int32[n], num_clusters int32, iterations int32)``:
    each vertex's component-minimum id (once the loop converges within
    ``max_iters``), the number of vertices that are their own label, and
    the loop's iteration count. ``rows`` runs the loop on the first ``rows``
    edges only (all of them when None); it is exact when no kept edge lies
    past them. A module-level jit keys the program on the input shapes and
    the static ``n``/``max_iters``/``rows``, so repeated calls (one per
    mining session) neither retrace nor relower the loop.
    """
    u, v, keep = edges[:rows, 0], edges[:rows, 1], keep[:rows]

    def body(state):
        labels, _, it = state
        lu = jnp.take(labels, u)
        lv = jnp.take(labels, v)
        new_edge_label = jnp.minimum(lu, lv)
        src_u = jnp.where(keep, new_edge_label, lu)
        src_v = jnp.where(keep, new_edge_label, lv)
        new = labels.at[u].min(src_u)
        new = new.at[v].min(src_v)
        # pointer jumping: labels <- labels[labels] (halves chain length)
        new = jnp.take(new, new)
        changed = jnp.any(new != labels)
        return new, changed, it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < max_iters)

    ids = jnp.arange(n, dtype=jnp.int32)
    labels, _, iterations = jax.lax.while_loop(
        cond, body, (ids, jnp.bool_(True), jnp.int32(0)))
    # every vertex no kept edge touches is its own cluster (the paper counts
    # all clusters)
    return labels, jnp.sum(labels == ids), iterations


@functools.partial(jax.jit, static_argnames=("similarity",))
def _keep_mask(deg: jax.Array, edges: jax.Array, edge_cards: jax.Array,
               threshold: jax.Array, similarity: str) -> jax.Array:
    """bool[m]: each edge's similarity score passes ``threshold`` (float32,
    an argument so that every threshold shares one program)."""
    du = jnp.take(deg, edges[:, 0]).astype(jnp.float32)
    dv = jnp.take(deg, edges[:, 1]).astype(jnp.float32)
    score = similarity_from_cardinalities(edge_cards, du, dv, similarity)
    return score >= threshold


@jax.jit
def _kept_first(edges: jax.Array, keep: jax.Array):
    """``(edges, keep, kept)``: the edges stably sorted with the kept ones
    first, their keep flags, and the number kept. Stable, so the kept edges
    keep their canonical order (and the loop's scatters their locality)."""
    _, u, v = jax.lax.sort((jnp.logical_not(keep), edges[:, 0], edges[:, 1]),
                           num_keys=1, is_stable=True)
    kept = jnp.sum(keep, dtype=jnp.int32)
    return (jnp.stack([u, v], axis=1),
            jnp.arange(keep.shape[0], dtype=jnp.int32) < kept, kept)


def _kept_components(n: int, edges: jax.Array, keep: jax.Array,
                     max_iters: int = 200):
    """``_connected_components`` on a kept-first prefix of the edges.

    Returns ``(labels, num_clusters, iterations, kept, rows)``: the loop
    runs on the first ``rows = min(pow2_bucket(kept), m)`` edges after
    ``_kept_first`` (the non-kept ones among them are no-ops), with labels,
    cluster count and iteration count equal to a loop over all m edges.
    ``kept`` is read on the host; under an outer jit it cannot be, so the
    loop runs on every edge and ``kept`` and ``rows`` are None.
    """
    if isinstance(keep, jax.core.Tracer):
        return (*_connected_components(n, edges, keep, max_iters),
                None, None)
    edges, keep, kept = _kept_first(edges, keep)
    kept = int(kept)
    rows = min(eng.pow2_bucket(kept), edges.shape[0])
    return (*_connected_components(n, edges, keep, max_iters, rows),
            kept, rows)


def jarvis_patrick(graph: Graph, sketch: Optional[SketchSet] = None,
                   similarity: str = "common", threshold: float = 2.0,
                   plan: Optional[eng.EnginePlan] = None,
                   edge_cards: Optional[jax.Array] = None, **kw):
    """Returns (labels int32[n], num_clusters int32).

    similarity: 'common' (|N_u∩N_v| ≥ threshold), 'jaccard' or 'overlap'
    (ratio ≥ threshold). ``edge_cards`` lets a MiningSession reuse its
    shared per-edge cardinality pass. Outside an outer jit it reads the
    kept-edge count on the host once, to size the loop (``_kept_components``).
    """
    edges = graph.edges
    if edge_cards is None:
        plan = eng.resolve_plan(plan, graph, sketch, kw)
        edge_cards = eng.edge_cardinalities(graph, sketch, plan)
    with trace.span("jp.similarity"):
        keep = _keep_mask(graph.deg, edges, edge_cards,
                          jnp.asarray(threshold, jnp.float32), similarity)
    with trace.span("jp.label_propagation") as sp:
        labels, num, iterations, kept, rows = _kept_components(
            graph.n, edges, keep)
        # a host read, so only while tracing and never under an outer jit
        if trace.enabled() and kept is not None:
            sp.set(iterations=int(iterations), kept=kept, bucket=rows)
    return labels, num
