"""Private fixed-arity Bloom AND+popcount entry points (deprecated names).

The paper's CPU hot loop is `popcnt(AND(Bx, By))` over AVX lanes; on the
TPU it runs on the VPU as the fused dense pass in ``fused_expr.py``. This
module keeps the fixed-arity forms that predate the set-expression compiler:

  * ``_pairs_impl`` / ``_pairs3_impl``: dense [E, W] row pairs (triples)
    -> int32[E] AND+popcount.
  * ``_edge_impl`` / ``_edge3_impl``: sketch rows gathered by an edge
    (triple) index list -> int32[E] AND+popcount.

Each is a direct call of the fused pass with a literal AND, bypassing the
compiler's cache and pow2 padding, which is what makes them the golden
oracles the bit-identity tests compare compiled expressions against. The
public seam is ``repro.kernels.ops``; the old public names here remain
importable as ``DeprecationWarning`` shims.
"""
from __future__ import annotations

import functools
import warnings

import jax

from .fused_expr import fused_gather_popcount, fused_rows_popcount


def _and(vals):
    """Literal k-way AND of the operand word arrays."""
    return functools.reduce(lambda x, y: x & y, vals)


def _pairs_impl(a: jax.Array, b: jax.Array, *, block_e: int = 256,
                block_w: int = 512, interpret: bool = False) -> jax.Array:
    """uint32[E, W] x uint32[E, W] -> int32[E]."""
    return fused_rows_popcount((a, b), _and, block_e=block_e,
                               block_w=block_w, interpret=interpret)


def _pairs3_impl(a: jax.Array, b: jax.Array, c: jax.Array, *,
                 block_e: int = 256, block_w: int = 512,
                 interpret: bool = False) -> jax.Array:
    """3-way dense variant of :func:`_pairs_impl` -> int32[E]."""
    return fused_rows_popcount((a, b, c), _and, block_e=block_e,
                               block_w=block_w, interpret=interpret)


def _edge_impl(bloom: jax.Array, edges: jax.Array, *, block_e: int = 256,
               block_w: int = 512, interpret: bool = False) -> jax.Array:
    """uint32[n, W] sketch matrix + int32[E, 2] edges -> int32[E]."""
    return fused_gather_popcount(bloom, (edges[:, 0], edges[:, 1]), _and,
                                 block_e=block_e, block_w=block_w,
                                 interpret=interpret)


def _edge3_impl(bloom: jax.Array, triples: jax.Array, *,
                block_e: int = 256, block_w: int = 512,
                interpret: bool = False) -> jax.Array:
    """uint32[n, W] + int32[T, 3] triples -> int32[T] popcnt(Bu & Bv & Bw)."""
    return fused_gather_popcount(
        bloom, (triples[:, 0], triples[:, 1], triples[:, 2]), _and,
        block_e=block_e, block_w=block_w, interpret=interpret)


# ----------------------------------------------------------------------------
# deprecation shims for the old public (raw, unpadded) entrypoints
# ----------------------------------------------------------------------------

def _deprecated(old: str, new: str, impl):
    """Wrap a private impl as a ``DeprecationWarning``-emitting shim."""
    @functools.wraps(impl)
    def shim(*args, **kwargs):
        """Forward to the private impl after warning (deprecated name)."""
        warnings.warn(
            f"repro.kernels.bf_intersect.{old} is deprecated; use {new}",
            DeprecationWarning, stacklevel=2)
        return impl(*args, **kwargs)

    shim.__name__ = old
    shim.__qualname__ = old
    shim.__doc__ = (f"Deprecated alias of the raw kernel; use {new}. "
                    f"See ``repro.engine.setexpr`` for arbitrary expressions.")
    return shim


bf_intersect_pairs = _deprecated(
    "bf_intersect_pairs", "repro.kernels.ops.bf_intersect_pairs", _pairs_impl)
bf_intersect3_pairs = _deprecated(
    "bf_intersect3_pairs", "repro.kernels.ops.bf_intersect3_pairs",
    _pairs3_impl)
bf_edge_intersect = _deprecated(
    "bf_edge_intersect", "repro.kernels.ops.bf_edge_intersect", _edge_impl)
bf_edge_intersect3 = _deprecated(
    "bf_edge_intersect3", "repro.kernels.ops.bf_edge_intersect3", _edge3_impl)

__all__ = [
    "bf_edge_intersect", "bf_edge_intersect3", "bf_intersect_pairs",
    "bf_intersect3_pairs",
]
