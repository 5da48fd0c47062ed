"""Fused Pallas evaluation of set-algebra expressions over sketch rows.

The SISA layer's code generator target: ``repro.engine.setexpr`` lowers a
``SetExpr`` tree (k-way AND/OR/ANDNOT over Bloom rows, popcount-reduced) to
*one* call into this module instead of one hand-rolled kernel per workload.
Two entry points cover every current consumer:

  * :func:`fused_rows_popcount` — the dense pass: operand rows are
    materialized ``[E, W]`` matrices (the sweep-cut prefix filter is
    computed, not gathered), tiled (block_e × block_w); each grid step
    evaluates the bitwise tree on the VMEM blocks and accumulates the
    popcount over the word-tile grid axis.
  * :func:`fused_gather_popcount` — the gather form: XLA gathers each
    leaf's sketch rows (``jnp.take``) and feeds them to the dense pass.
    Mosaic DMAs only (8, 128)-aligned slices of a tiled HBM array, so an
    in-kernel gather of single sketch rows compiles only at W = 128; the
    XLA gather has no such limit.

Both take the expression as ``eval_fn``: a pure function from a tuple of
uint32 word arrays (one per leaf, identical shapes) to one uint32 word
array. The same callable evaluates the tree on VMEM blocks inside the kernel
and on gathered jnp arrays in the engine's jnp path, which is what makes
kernel/jnp popcounts bit-identical by construction.

Callers pass any ``E`` and ``W``: the dense pass picks block shapes the TPU
accepts (a block spans a whole axis or is a multiple of the (8, 128) tile),
zero-pads the rows (zero words add no bits) and slices the pad off. The
output is written as an ``[E, 1]`` column (a rank-1 output block is refused
by the chip's compiler) and returned flat. See
`docs/ARCHITECTURE.md <../../../docs/ARCHITECTURE.md#kernel-layer-the-set-expression-compiler>`__
for the data flow.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EvalFn = Callable[[Tuple[jax.Array, ...]], jax.Array]

SUBLANES = 8      # second-minor tile of a 32-bit TPU array
LANES = 128       # minor tile


def default_interpret() -> bool:
    """Pallas interpret mode everywhere but a TPU backend.

    Asked at call time, never at import, so the answer follows the
    platform the program configured.
    """
    return jax.default_backend() != "tpu"


def _block(size: int, block: int, tile: int) -> int:
    """A legal block length along one axis: the whole axis when it fits in
    one block, else ``block`` rounded up to a multiple of ``tile``."""
    if size <= block:
        return size
    return -(-block // tile) * tile


def _rows_expr_kernel(*refs, eval_fn: EvalFn):
    """Dense kernel body: evaluate the expression on the operand blocks and
    popcount-accumulate into the ``[block_e, 1]`` output column over the
    word-tile grid axis."""
    *in_refs, o_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        """Zero the per-block output on the first word-grid step."""
        o_ref[...] = jnp.zeros_like(o_ref)

    cnt = jax.lax.population_count(eval_fn(tuple(r[...] for r in in_refs)))
    o_ref[...] += jnp.sum(cnt.astype(jnp.int32), axis=1, keepdims=True)


def fused_rows_popcount(rows: Sequence[jax.Array], eval_fn: EvalFn, *,
                        block_e: int = 256, block_w: int = 512,
                        interpret: bool = False) -> jax.Array:
    """One fused pass over dense operand rows: int32[E] popcounts.

    Args:
      rows:     one uint32[E, W] operand matrix per expression leaf (already
                materialized — e.g. the sweep cut's computed prefix filter).
      eval_fn:  bitwise expression evaluator over the operand blocks.
      block_e:  rows per grid step (rounded up to a multiple of 8 when E
                spans more than one block).
      block_w:  words per grid step (rounded up to a multiple of 128 when W
                spans more than one block).
      interpret: run the kernel body in Python (non-TPU backends).

    Returns:
      int32[E] — popcount of the evaluated expression row per input row.
    """
    e, w = rows[0].shape
    be = _block(e, block_e, SUBLANES)
    bw = _block(w, block_w, LANES)
    e_pad, w_pad = -(-e // be) * be, -(-w // bw) * bw
    if (e_pad, w_pad) != (e, w):
        rows = [jnp.pad(r, ((0, e_pad - e), (0, w_pad - w))) for r in rows]
    spec = pl.BlockSpec((be, bw), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_rows_expr_kernel, eval_fn=eval_fn),
        grid=(e_pad // be, w_pad // bw),
        in_specs=[spec] * len(rows),
        out_specs=pl.BlockSpec((be, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e_pad, 1), jnp.int32),
        interpret=interpret,
    )(*rows)
    return out[:e, 0]


def fused_gather_popcount(bloom: jax.Array, cols: Sequence[jax.Array],
                          eval_fn: EvalFn, *, block_e: int = 256,
                          block_w: int = 512,
                          interpret: bool = False) -> jax.Array:
    """Gather each leaf's sketch rows, then one fused dense pass: int32[T].

    Args:
      bloom:    uint32[n, W] sketch matrix.
      cols:     one int32[T] row-index array per expression leaf.
      eval_fn:  bitwise expression evaluator over the gathered rows.
      block_e:  tuples per grid step of the dense pass.
      block_w:  sketch words per grid step of the dense pass.
      interpret: run the kernel body in Python (non-TPU backends).

    Returns:
      int32[T] — popcount of the evaluated expression row per tuple.
    """
    return fused_rows_popcount(
        [jnp.take(bloom, c, axis=0) for c in cols], eval_fn,
        block_e=block_e, block_w=block_w, interpret=interpret)


__all__ = ["EvalFn", "default_interpret", "fused_gather_popcount",
           "fused_rows_popcount"]
