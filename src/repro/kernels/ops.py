"""Canonical public kernel entrypoints (padded, compiled via ``setexpr``).

This module is the *one* public seam over the Pallas sketch kernels. The
Bloom-filter popcount family (`bf_*`) no longer binds one hand-rolled kernel
per workload: each entrypoint builds the equivalent set expression and asks
``repro.engine.setexpr`` for the cached compiled form, which lowers to one
fused VMEM pass (``repro.kernels.fused_expr``). On non-TPU backends the
fused pass runs in Pallas interpret mode so correctness is validated
everywhere; on TPU it compiles to Mosaic (checked ahead of time for v5e by
``tests/test_tpu_compile.py``). Inputs are padded to pow2/block multiples
inside the compiled object and the pad is sliced off, so callers never see
blocking constraints.

Tuning knobs (``block_e``, ``block_w``, ``interpret``) are keyword-only.
The former raw duplicates in ``bf_intersect.py`` (same names, unpadded
signatures) are now ``DeprecationWarning`` shims; new code — including any
new workload — should either call these entrypoints or compile its own
expression with ``repro.engine.setexpr.compile_expr``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import mh_intersect as _mh
from .fused_expr import default_interpret


def _pad_rows(x: jax.Array, mult: int, fill=0) -> jax.Array:
    """Pad the leading axis to a multiple of ``mult`` with ``fill``."""
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad, *x.shape[1:]), fill, x.dtype)], axis=0)


def _pad_cols(x: jax.Array, mult: int, fill=0) -> jax.Array:
    """Pad the trailing axis to a multiple of ``mult`` with ``fill``."""
    pad = (-x.shape[1]) % mult
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((x.shape[0], pad), fill, x.dtype)], axis=1)


def _compiled_and(k: int, *, block_e: int, block_w: int,
                  interpret: Optional[bool]):
    """The cached compiled k-way AND expression (lazy engine import —
    ``repro.engine`` imports this module, so the reverse edge stays inside
    the function body)."""
    from ..engine import setexpr

    return setexpr.compile_expr(setexpr.and_all(*setexpr.rows(k)),
                                block_e=block_e, block_w=block_w,
                                use_kernel=True, interpret=interpret)


def bf_intersect_pairs(a: jax.Array, b: jax.Array, *, block_e: int = 256,
                       block_w: int = 512,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Dense AND+popcount: uint32[E, W] x uint32[E, W] -> int32[E].

    Lowered as the compiled 2-way AND expression in dense (``ones_rows``)
    form — one fused pass, no blocking constraints on E or W.
    """
    return _compiled_and(2, block_e=block_e, block_w=block_w,
                         interpret=interpret).ones_rows(a, b)


def bf_intersect3_pairs(a: jax.Array, b: jax.Array, c: jax.Array, *,
                        block_e: int = 256, block_w: int = 512,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Dense 3-way AND+popcount over row-aligned operands -> int32[E]."""
    return _compiled_and(3, block_e=block_e, block_w=block_w,
                         interpret=interpret).ones_rows(a, b, c)


def bf_edge_intersect(bloom: jax.Array, edges: jax.Array, *,
                      block_e: int = 256, block_w: int = 512,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Gathered AND+popcount over an edge list -> int32[E].

    Lowered as the compiled 2-way AND expression in gather form: edge
    endpoints index sketch rows, which feed one fused dense pass.
    """
    return _compiled_and(2, block_e=block_e, block_w=block_w,
                         interpret=interpret).ones(bloom, edges)


def bf_edge_intersect3(bloom: jax.Array, triples: jax.Array, *,
                       block_e: int = 256, block_w: int = 512,
                       interpret: Optional[bool] = None) -> jax.Array:
    """3-way gathered AND+popcount over (u, v, w) triples (4-clique path)."""
    return _compiled_and(3, block_e=block_e, block_w=block_w,
                         interpret=interpret).ones(bloom, triples)


@functools.partial(jax.jit, static_argnames=("sentinel", "block_e"))
def mh_intersect_pairs(a: jax.Array, b: jax.Array, sentinel: int, *,
                       block_e: int = 128) -> jax.Array:
    """MinHash signature match count per row pair -> int32[E]."""
    e = a.shape[0]
    be = min(block_e, max(e, 1))
    a2 = _pad_rows(a, be, fill=sentinel)
    b2 = _pad_rows(b, be, fill=sentinel)
    out = _mh.mh_intersect_pairs(a2, b2, sentinel, block_e=be,
                                 interpret=default_interpret())
    return out[:e]


@functools.partial(jax.jit, static_argnames=("sentinel", "block_e"))
def khash_match_pairs(a: jax.Array, b: jax.Array, sentinel: int, *,
                      block_e: int = 512) -> jax.Array:
    """Sorted k-hash sample intersection count per row pair -> int32[E]."""
    e = a.shape[0]
    be = min(block_e, max(e, 1))
    a2 = _pad_rows(a, be, fill=sentinel)
    b2 = _pad_rows(b, be, fill=sentinel)
    out = _mh.khash_match_pairs(a2, b2, sentinel, block_e=be,
                                interpret=default_interpret())
    return out[:e]


__all__ = [
    "bf_edge_intersect", "bf_edge_intersect3", "bf_intersect_pairs",
    "bf_intersect3_pairs", "khash_match_pairs", "mh_intersect_pairs",
]
