"""Set-expression IR + compiler over the Pallas sketch kernels (SISA layer).

ProbGraph turns vertex-set operations into sketch bitwise algebra; SISA's
observation is that a *small set-centric instruction set* — not one kernel
per workload — is the right abstraction. This module is that instruction
set: a tiny IR of :class:`SetExpr` nodes (k-way ``AND``/``OR``/``ANDNOT``
over sketch rows, implicitly popcount-reduced) and a compiler that lowers
any expression tree to **one** fused Pallas VMEM pass
(:mod:`repro.kernels.fused_expr`) — the referenced sketch rows gathered
per tuple, bitwise evaluation in registers, popcount reduction — or to the
equivalent jnp gather when the plan stays off the kernel path. Kernel and
jnp lowerings evaluate the *same* expression closure on the same integers,
so their popcounts are bit-identical by construction.

The three formerly hand-rolled kernels are expressions here::

    rows(2)[0] & rows(2)[1]                # 2-way AND: edge cardinalities
    and_all(*rows(3))                      # 3-way AND: 4-clique triples
    rows(2)[0] & rows(2)[1]  (dense form)  # sweep-cut prefix-OR gating

and the 4-way AND behind 5-clique counting needed no new kernel — that is
the API earning its keep.

Compiled objects are cached (module-level, keyed by expression *structure*
plus block shapes and dispatch flags) and pad the tuple axis to power-of-two
buckets, so arbitrary workload sizes reuse a bounded set of compiled
programs — the same discipline as ``plan.pow2_bucket`` everywhere else.

Usage::

    from repro.engine import setexpr
    u, v, w = setexpr.rows(3)
    ce = setexpr.compile_expr((u & v) - w)       # |N_u ∩ N_v ∖ bits(B_w)|
    ones = ce.ones(sketch.data, tuples)          # int32[T] popcounts
    size = ce.cardinality(sketch, tuples)        # Swamidass estimate
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from typing import Callable, Dict, Optional, Sequence, Tuple

from ..kernels import fused_expr
from ..obs import trace
from ..obs.metrics import REGISTRY
from .plan import pow2_bucket


# ----------------------------------------------------------------------------
# the IR
# ----------------------------------------------------------------------------

class SetExpr:
    """Base class of set-algebra expression nodes over sketch rows.

    Supports operator sugar: ``a & b`` (intersection/AND), ``a | b``
    (union/OR), ``a - b`` (difference/ANDNOT). Expressions are immutable
    and hash by structure, which is what the compile cache keys on.
    """

    def __and__(self, other: "SetExpr") -> "SetExpr":
        """k-way AND; chains flatten (``a & b & c`` is one 3-way node)."""
        return and_all(self, other)

    def __or__(self, other: "SetExpr") -> "SetExpr":
        """k-way OR; chains flatten like AND."""
        return or_all(self, other)

    def __sub__(self, other: "SetExpr") -> "SetExpr":
        """Set difference lowered as ANDNOT: ``a & ~b`` on the bit rows."""
        return AndNot(self, other)

    def key(self) -> tuple:
        """Canonical structure key (nested tuples) — the cache identity."""
        raise NotImplementedError

    def __hash__(self) -> int:
        return hash(self.key())

    def __eq__(self, other) -> bool:
        return isinstance(other, SetExpr) and self.key() == other.key()


class Row(SetExpr):
    """A leaf: the sketch row of tuple column ``slot`` (0-based)."""

    def __init__(self, slot: int):
        if slot < 0:
            raise ValueError("Row slot must be >= 0")
        self.slot = int(slot)

    def key(self) -> tuple:
        """``("row", slot)``."""
        return ("row", self.slot)

    def __repr__(self) -> str:
        return f"Row({self.slot})"


class _NAry(SetExpr):
    """Internal k-way node (``op`` is "and" | "or"); built via the
    :func:`and_all` / :func:`or_all` constructors, which flatten chains."""

    def __init__(self, op: str, args: Tuple[SetExpr, ...]):
        self.op = op
        self.args = args

    def key(self) -> tuple:
        """``(op, child_key, ...)``."""
        return (self.op, *(a.key() for a in self.args))

    def __repr__(self) -> str:
        sep = " & " if self.op == "and" else " | "
        return "(" + sep.join(map(repr, self.args)) + ")"


class AndNot(SetExpr):
    """Binary difference node: bits of ``a`` with ``b``'s bits cleared."""

    def __init__(self, a: SetExpr, b: SetExpr):
        self.a = a
        self.b = b

    def key(self) -> tuple:
        """``("andnot", a_key, b_key)``."""
        return ("andnot", self.a.key(), self.b.key())

    def __repr__(self) -> str:
        return f"({self.a!r} - {self.b!r})"


def rows(k: int) -> Tuple[Row, ...]:
    """The first ``k`` leaf rows — ``rows(3)`` ≡ ``(Row(0), Row(1), Row(2))``."""
    return tuple(Row(i) for i in range(k))


def _flatten(op: str, args: Sequence[SetExpr]) -> Tuple[SetExpr, ...]:
    out: list[SetExpr] = []
    for a in args:
        if isinstance(a, _NAry) and a.op == op:
            out.extend(a.args)
        else:
            out.append(a)
    return tuple(out)


def and_all(*args: SetExpr) -> SetExpr:
    """k-way AND of the given expressions (nested ANDs flatten)."""
    flat = _flatten("and", args)
    return flat[0] if len(flat) == 1 else _NAry("and", flat)


def or_all(*args: SetExpr) -> SetExpr:
    """k-way OR of the given expressions (nested ORs flatten)."""
    flat = _flatten("or", args)
    return flat[0] if len(flat) == 1 else _NAry("or", flat)


def expr_slots(expr: SetExpr) -> Tuple[int, ...]:
    """Sorted distinct tuple columns the expression reads (its leaves)."""
    found: set[int] = set()

    def walk(e: SetExpr) -> None:
        """Collect leaf slots depth-first."""
        if isinstance(e, Row):
            found.add(e.slot)
        elif isinstance(e, _NAry):
            for a in e.args:
                walk(a)
        elif isinstance(e, AndNot):
            walk(e.a)
            walk(e.b)
        else:  # pragma: no cover - new node kinds must extend the walker
            raise TypeError(f"unknown SetExpr node {type(e).__name__}")

    walk(expr)
    return tuple(sorted(found))


def _make_eval(expr: SetExpr, pos: Dict[int, int]
               ) -> Callable[[Tuple[jax.Array, ...]], jax.Array]:
    """Build the bitwise evaluator closure: slab tuple -> uint32 word array.

    The closure is pure jnp ops (&, |, ~) so the *same* function body runs
    on VMEM slab values inside the fused kernel and on gathered rows in the
    jnp fallback — the source of kernel/jnp bit-identity.
    """
    def ev(e: SetExpr, vals: Tuple[jax.Array, ...]) -> jax.Array:
        """Recursive structural evaluation."""
        if isinstance(e, Row):
            return vals[pos[e.slot]]
        if isinstance(e, _NAry):
            acc = ev(e.args[0], vals)
            for a in e.args[1:]:
                acc = (acc & ev(a, vals)) if e.op == "and" \
                    else (acc | ev(a, vals))
            return acc
        if isinstance(e, AndNot):
            return ev(e.a, vals) & ~ev(e.b, vals)
        raise TypeError(f"unknown SetExpr node {type(e).__name__}")

    return lambda vals: ev(expr, vals)


# ----------------------------------------------------------------------------
# the compiler
# ----------------------------------------------------------------------------

def _pad_axis0(x: jax.Array, to: int, fill=0) -> jax.Array:
    """Zero-fill (or ``fill``-fill) the leading axis up to length ``to``."""
    pad = to - x.shape[0]
    if pad <= 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad, *x.shape[1:]), fill, x.dtype)], axis=0)


class CompiledSetExpr:
    """One expression lowered to a fused popcount pass (plus jnp fallback).

    Instances come from :func:`compile_expr` (which caches them by
    expression structure + block shapes + dispatch flags — do not construct
    directly unless you want to bypass the cache). Two input forms:

      * :meth:`ones` — *gather* form: sketch matrix + int32[T, >max_slot]
        tuple array; each leaf ``Row(s)`` reads the sketch row indexed by
        tuple column ``s``.
      * :meth:`ones_rows` — *dense* form: one uint32[E, W] operand matrix
        per distinct leaf slot, in sorted-slot order (for operands that are
        computed rather than resident in the sketch matrix, like the sweep
        cut's prefix filter).

    The tuple/row axis is padded to a pow2 bucket so varying workload
    sizes share compiled programs; the kernel pads the word axis with zero
    words, which add no bits to any popcount.
    """

    def __init__(self, expr: SetExpr, *, block_e: int, block_w: int,
                 use_kernel: bool, interpret: Optional[bool] = None):
        self.expr = expr
        self.slots = expr_slots(expr)
        if not self.slots:
            raise ValueError("expression references no Row leaves")
        self.arity = len(self.slots)
        self.block_e = int(block_e)
        self.block_w = int(block_w)
        self.use_kernel = bool(use_kernel)
        self.interpret = (fused_expr.default_interpret() if interpret is None
                          else bool(interpret))
        self._eval = _make_eval(expr, {s: i for i, s in enumerate(self.slots)})
        self._ones_jit = jax.jit(self._ones_impl)
        self._rows_jit = jax.jit(self._ones_rows_impl)

    # -- gather form --------------------------------------------------------

    def _ones_impl(self, data: jax.Array, tuples: jax.Array) -> jax.Array:
        """Padded lowering of the gather form (jitted per input shape)."""
        t = tuples.shape[0]
        if self.use_kernel:
            # pad with row 0 (always present); the pad's counts are sliced off
            cols = [_pad_axis0(tuples[:, s], pow2_bucket(t))
                    for s in self.slots]
            out = fused_expr.fused_gather_popcount(
                data, cols, self._eval, block_e=self.block_e,
                block_w=self.block_w, interpret=self.interpret)
            return out[:t]
        vals = tuple(jnp.take(data, tuples[:, s], axis=0)
                     for s in self.slots)
        return jnp.sum(jax.lax.population_count(self._eval(vals)),
                       axis=-1).astype(jnp.int32)

    def ones(self, data: jax.Array, tuples: jax.Array) -> jax.Array:
        """Evaluate over gathered sketch rows: int32[T] popcounts.

        Args:
          data:   uint32[n, W] sketch matrix (e.g. ``SketchSet.data``).
          tuples: int32[T, k] row-index tuples; leaf ``Row(s)`` reads
                  column ``s`` (k must exceed the largest referenced slot).
        """
        tuples = jnp.asarray(tuples, jnp.int32)
        if tuples.shape[0] == 0:
            return jnp.zeros((0,), jnp.int32)
        if tuples.shape[1] <= self.slots[-1]:
            raise ValueError(
                f"expression reads tuple column {self.slots[-1]} but tuples "
                f"have width {tuples.shape[1]}")
        return self._ones_jit(data, tuples)

    def cardinality(self, sketch, tuples: jax.Array) -> jax.Array:
        """Swamidass size estimate of the expression row per tuple.

        Exact for the AND family (paper Eq. 2 applied to the k-way AND
        row); for OR/ANDNOT rows it is the same ones→size map applied to
        the evaluated bit row — see ``core.bounds.bf_kway_and_mse_bound``
        for when this is quantitatively trustworthy.
        """
        from ..core import estimators as est
        return est.bf_intersection_and_from_ones(
            self.ones(sketch.data, tuples), sketch.total_bits,
            sketch.num_hashes)

    # -- dense form ---------------------------------------------------------

    def _ones_rows_impl(self, *rows: jax.Array) -> jax.Array:
        """Padded lowering of the dense form (jitted per input shape)."""
        e = rows[0].shape[0]
        if self.use_kernel:
            padded = [_pad_axis0(r, pow2_bucket(e)) for r in rows]
            out = fused_expr.fused_rows_popcount(
                padded, self._eval, block_e=self.block_e,
                block_w=self.block_w, interpret=self.interpret)
            return out[:e]
        return jnp.sum(jax.lax.population_count(self._eval(tuple(rows))),
                       axis=-1).astype(jnp.int32)

    def ones_rows(self, *rows: jax.Array) -> jax.Array:
        """Evaluate over dense operand matrices: int32[E] popcounts.

        Args:
          *rows: one uint32[E, W] matrix per distinct leaf slot, in sorted
                 slot order (``Row(0)``'s operand first).
        """
        if len(rows) != self.arity:
            raise ValueError(
                f"expression has {self.arity} distinct leaves, got "
                f"{len(rows)} operand matrices")
        if rows[0].shape[0] == 0:
            return jnp.zeros((0,), jnp.int32)
        return self._rows_jit(*rows)

    def __repr__(self) -> str:
        return (f"CompiledSetExpr({self.expr!r}, block_e={self.block_e}, "
                f"block_w={self.block_w}, use_kernel={self.use_kernel})")


# the shared compile cache: expression structure + block shapes + dispatch
_CACHE: Dict[tuple, CompiledSetExpr] = {}
_CACHE_HITS = 0


def compile_expr(expr: SetExpr, *, block_e: int = 256, block_w: int = 512,
                 use_kernel: bool = True,
                 interpret: Optional[bool] = None) -> CompiledSetExpr:
    """Compile (with caching) a set expression to a fused popcount pass.

    Args:
      expr:       the expression tree (see :func:`rows` and the operators).
      block_e:    tuples/rows per Pallas grid step (keyword-only knob).
      block_w:    sketch words per grid step (keyword-only knob).
      use_kernel: lower to the fused Pallas pass; ``False`` lowers to the
                  equivalent jnp gather + popcount (bit-identical ints).
      interpret:  force Pallas interpret mode (default: auto — interpret on
                  non-TPU backends).

    Returns:
      The cached :class:`CompiledSetExpr` for this structure/configuration —
      repeated compiles of the same shape of query are free, and their
      jitted programs (bounded by pow2 size buckets) are shared process-wide.
    """
    global _CACHE_HITS
    key = (expr.key(), int(block_e), int(block_w), bool(use_kernel),
           interpret)
    hit = _CACHE.get(key)
    if hit is not None:
        _CACHE_HITS += 1
        REGISTRY.counter("setexpr_compile_total", result="hit").inc()
        return hit
    with trace.span("setexpr.compile", expr=repr(expr)):
        ce = CompiledSetExpr(expr, block_e=block_e, block_w=block_w,
                             use_kernel=use_kernel, interpret=interpret)
    REGISTRY.counter("setexpr_compile_total", result="miss").inc()
    _CACHE[key] = ce
    return ce


def cache_info() -> dict:
    """Compile-cache counters: distinct compiled expressions and hits."""
    return {"size": len(_CACHE), "hits": _CACHE_HITS}


def cache_clear() -> None:
    """Drop every cached compiled expression (mainly for tests)."""
    global _CACHE_HITS
    _CACHE.clear()
    _CACHE_HITS = 0


__all__ = [
    "AndNot", "CompiledSetExpr", "Row", "SetExpr", "and_all", "cache_clear",
    "cache_info", "compile_expr", "expr_slots", "or_all", "rows",
]
