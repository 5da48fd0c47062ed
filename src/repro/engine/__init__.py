"""Batched mining engine: the shared execution seam for ProbGraph algorithms.

``EnginePlan`` describes *how* set-intersection work runs (batching, Pallas
block shapes, estimator dispatch, edge-axis sharding); ``session`` amortizes
one sketch build across many queries; ``setexpr`` is the set-expression
compiler every sketch popcount routes through. Downstream packages
(``launch``, ``stream``) should import from :mod:`repro.engine.api`, the
facade that pins the supported surface. See engine.py for the full story.
"""
from . import api, setexpr
from .plan import (EnginePlan, fold_edges, fold_edges_masked, map_edges,
                   order_edges_by_hub, plan_for, pow2_bucket)
from .engine import (
    DeviceCarry,
    Footprint,
    MiningSession,
    edge_cardinalities,
    pair_cardinality_fn,
    resolve_plan,
    session,
    sum_edge_cardinalities,
    triple_cardinality_ones,
    tuple_cardinality_ones,
    wedge_quad_ones,
)

__all__ = [
    "DeviceCarry", "EnginePlan", "Footprint", "MiningSession", "api",
    "edge_cardinalities",
    "fold_edges", "fold_edges_masked", "map_edges", "order_edges_by_hub",
    "pair_cardinality_fn", "plan_for", "pow2_bucket", "resolve_plan",
    "session", "setexpr", "sum_edge_cardinalities",
    "triple_cardinality_ones", "tuple_cardinality_ones", "wedge_quad_ones",
]
