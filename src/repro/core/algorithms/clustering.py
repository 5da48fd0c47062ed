"""Jarvis–Patrick clustering (paper Listing 4).

Two vertices u, v end up in the same cluster iff they are adjacent AND their
vertex similarity passes a threshold. Similarity ∈ {common (|N_u∩N_v| ≥ τ),
jaccard, overlap} — all driven by the |X∩Y| provider, exact or sketched.

Connected components over the kept edges run as data-parallel min-label
propagation (scatter-min + gather until fixpoint) — the shared-memory
union-find of the CPU implementation does not map to SPMD; label propagation
has depth O(diameter·log n) and is the standard XLA-friendly CC.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ... import engine as eng
from ...obs import trace
from ..graph import Graph
from ..sketches import SketchSet


def _connected_components(n: int, edges: jax.Array, keep: jax.Array,
                          max_iters: int = 200) -> jax.Array:
    u, v = edges[:, 0], edges[:, 1]

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

    labels0 = jnp.arange(n, dtype=jnp.int32)
    labels, _, _ = jax.lax.while_loop(
        cond, body, (labels0, jnp.bool_(True), jnp.int32(0)))
    return labels


def jarvis_patrick(graph: Graph, sketch: Optional[SketchSet] = None,
                   similarity: str = "common", threshold: float = 2.0,
                   plan: Optional[eng.EnginePlan] = None,
                   edge_cards: Optional[jax.Array] = None, **kw):
    """Returns (labels int32[n], num_clusters int32).

    similarity: 'common' (|N_u∩N_v| ≥ threshold), 'jaccard' or 'overlap'
    (ratio ≥ threshold). ``edge_cards`` lets a MiningSession reuse its
    shared per-edge cardinality pass.
    """
    from .similarity import similarity_from_cardinalities

    edges = graph.edges
    if edge_cards is None:
        plan = eng.resolve_plan(plan, graph, sketch, kw)
        edge_cards = eng.edge_cardinalities(graph, sketch, plan)
    with trace.span("jp.similarity"):
        du = jnp.take(graph.deg, edges[:, 0]).astype(jnp.float32)
        dv = jnp.take(graph.deg, edges[:, 1]).astype(jnp.float32)
        score = similarity_from_cardinalities(edge_cards, du, dv, similarity)
        keep = score >= threshold
    with trace.span("jp.label_propagation"):
        labels = _connected_components(graph.n, edges, keep)
    # count distinct labels among non-isolated semantics: every vertex is its
    # own cluster when no kept edge touches it (paper counts all clusters)
    num = jnp.sum(labels == jnp.arange(graph.n, dtype=jnp.int32))
    return labels, num
