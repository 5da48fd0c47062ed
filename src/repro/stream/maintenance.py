"""Incremental sketch maintenance for streaming graphs.

ProbGraph's representations are cheap to *maintain*, not just to intersect:

  * Bloom inserts are monotone — scatter-OR only the new elements' bit
    positions into the touched rows.
  * k-Hash inserts are lexicographic (hash, element) min-merges per hash fn.
  * 1-Hash inserts are sorted merges of (hash, element) pairs, keep-k.
  * KMV inserts are sorted merges of unit-interval hash values, keep-k.

All four incremental updates are **bit-identical** to a from-scratch rebuild
on the post-insert adjacency (the builders' tie-breaking — stable argsort /
first-argmin over id-sorted rows — equals the (hash, element) lexicographic
order used here), which the property tests assert per kind.

Deletions are not monotone: a deleted element may be the very minimum a row
stores. Deletion therefore marks rows *dirty* and defers work: each dirty
row tracks how many deleted-but-still-sketched (phantom) elements it holds,
and an :class:`ErrorBudgetPolicy` — driven by the paper's own accuracy
bounds in ``core.bounds`` — decides when the accumulated staleness exceeds
the sketch's intrinsic error scale and the row must be selectively rebuilt
through the existing chunked builders (only dirty rows, never the full
O(b·Σd_v) pass).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bounds
from ..core.hashing import hash_u32, hash_unit_interval
from ..core.sketches import (KMV_PAD, PAD_HASH, SketchSet, _map_vertex_chunks,
                             bloom_bits, bloom_rows, bloom_words_for_budget,
                             khash_rows, kmv_rows, minhash_k_for_budget,
                             onehash_rows, onehash_values, pack_bits)
from ..engine.api import pow2_bucket
from ..obs import trace
from .dynamic_graph import DeltaResult, DynamicGraph


# ----------------------------------------------------------------------------
# error-budget policy (core.bounds-driven deferral of deletion rebuilds)
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ErrorBudgetPolicy:
    """When must a dirty (deletion-pending) sketch row be rebuilt?

    Every phantom element (deleted from the graph, still in the sketch)
    perturbs any |N_u ∩ N_v| estimate through that row by at most 1, so a
    row's stale count is an additive error bound on its answers. The policy
    tolerates staleness up to ``rel_tolerance`` × the sketch's own
    statistical error scale at the row's degree (Prop IV.1 RMSE for Bloom,
    inverted Prop IV.2 for MinHash/KMV): deferred deletions hide below the
    estimator's intrinsic noise floor.

    ``rel_tolerance=0`` (the default) rebuilds every dirty row immediately —
    strict mode, streaming answers stay bit-identical to a from-scratch
    build. ``max_stale`` is an absolute cap independent of degree.
    """

    rel_tolerance: float = 0.0
    confidence: float = 0.05
    max_stale: int = 1 << 30

    def allowed_stale(self, sketch: SketchSet, degrees: np.ndarray) -> np.ndarray:
        """Per-row stale-count budget at the given degrees (0 = strict)."""
        if self.rel_tolerance <= 0.0:
            return np.zeros(np.shape(degrees), dtype=np.float64)
        if sketch.kind == "bf":
            scale = bounds.bf_and_rmse(degrees, sketch.total_bits,
                                       sketch.num_hashes)
        else:
            scale = bounds.minhash_error_scale(degrees, sketch.k,
                                               self.confidence)
        return np.minimum(self.rel_tolerance * scale, float(self.max_stale))


#: rebuild-immediately policy: streaming ≡ from-scratch, bit for bit
STRICT_POLICY = ErrorBudgetPolicy(rel_tolerance=0.0)


# ----------------------------------------------------------------------------
# batched device update kernels (one per sketch kind)
# ----------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "num_hashes", "seed",
                                             "total_bits"))
def _bloom_insert(data, rows, new_elems, *, n, num_hashes, seed, total_bits):
    """Scatter-OR only the new elements' bit positions into the given rows."""
    bits = bloom_bits(new_elems, n, num_hashes, total_bits, seed)
    cur = jnp.take(data, rows, axis=0)
    # padded entries carry row index n (out of range) and are dropped
    return data.at[rows].set(cur | pack_bits(bits), mode="drop")


@functools.partial(jax.jit, static_argnames=("n", "seed"))
def _khash_insert(data, rows, new_elems, *, n, seed):
    """Per-hash-fn lexicographic (hash, element) min-merge of new elements."""
    k = data.shape[1]
    cur = jnp.take(data, rows, axis=0)                       # [T, k]
    seeds = (jnp.arange(k, dtype=jnp.uint32)
             + jnp.uint32(seed) * jnp.uint32(0x9E3779B9))
    cur_valid = cur < n
    cur_h = jnp.where(cur_valid,
                      hash_u32(jnp.where(cur_valid, cur, 0), seeds), PAD_HASH)
    nvalid = new_elems < n
    safe = jnp.where(nvalid, new_elems, 0)
    h = hash_u32(safe[..., None], seeds)                     # [T, L, k]
    h = jnp.where(nvalid[..., None], h, PAD_HASH)
    # first-argmin over id-sorted new elements == lexicographic (h, elem) min
    arg = jnp.argmin(h, axis=1)                              # [T, k]
    e_new = jnp.take_along_axis(new_elems, arg, axis=1)
    h_new = jnp.take_along_axis(h, arg[:, None, :], axis=1)[:, 0, :]
    better = (h_new < cur_h) | ((h_new == cur_h) & (e_new < cur))
    return data.at[rows].set(jnp.where(better, e_new, cur).astype(jnp.int32),
                             mode="drop")


@functools.partial(jax.jit, static_argnames=("n", "seed"))
def _onehash_insert(data, rows, new_elems, *, n, seed):
    """Sorted (hash, element) merge of current k-set with new elements."""
    k = data.shape[1]
    cur = jnp.take(data, rows, axis=0)
    cur_h = onehash_values(cur, n, seed)
    nvalid = new_elems < n
    new_h = jnp.where(nvalid,
                      hash_u32(jnp.where(nvalid, new_elems, 0),
                               jnp.uint32(seed)), PAD_HASH)
    elems = jnp.concatenate([cur, jnp.where(nvalid, new_elems, n)], axis=1)
    hs = jnp.concatenate([cur_h, new_h], axis=1)
    order = jnp.lexsort((elems, hs), axis=-1)[:, :k]
    sel_e = jnp.take_along_axis(elems, order, axis=1)
    sel_h = jnp.take_along_axis(hs, order, axis=1)
    return data.at[rows].set(
        jnp.where(sel_h == PAD_HASH, n, sel_e).astype(jnp.int32), mode="drop")


@functools.partial(jax.jit, static_argnames=("n", "seed"))
def _kmv_insert(data, rows, new_elems, *, n, seed):
    """Sorted merge of current k smallest hash values with new ones."""
    k = data.shape[1]
    cur = jnp.take(data, rows, axis=0)
    nvalid = new_elems < n
    nh = jnp.where(nvalid,
                   hash_unit_interval(jnp.where(nvalid, new_elems, 0),
                                      jnp.uint32(seed)), KMV_PAD)
    merged = jnp.sort(jnp.concatenate([cur, nh], axis=1), axis=1)[:, :k]
    return data.at[rows].set(merged, mode="drop")


# ----------------------------------------------------------------------------
# maintainer
# ----------------------------------------------------------------------------

class SketchMaintainer:
    """Owns one sketch of a :class:`DynamicGraph` and keeps it current.

    Inserts are absorbed incrementally (per-kind device merges above);
    deletions mark rows dirty and are repaired by selective rebuild of only
    the dirty rows through the chunked batch builders, when the
    :class:`ErrorBudgetPolicy` says their staleness is no longer affordable.
    """

    def __init__(self, dyn: DynamicGraph, kind: str = "bf",
                 storage_budget: float = 0.25, num_hashes: int = 2,
                 seed: int = 0, words: Optional[int] = None,
                 k: Optional[int] = None,
                 policy: Optional[ErrorBudgetPolicy] = None,
                 chunk: int = 4096, data: Optional[jnp.ndarray] = None):
        if kind not in ("bf", "kh", "1h", "kmv"):
            raise ValueError(f"unknown sketch kind: {kind}")
        self.dyn = dyn
        self.kind = kind
        self.num_hashes = int(num_hashes)
        self.seed = int(seed)
        self.policy = policy if policy is not None else STRICT_POLICY
        self.chunk = int(chunk)
        n, m = dyn.n, dyn.m
        if kind == "bf":
            self.words = int(words) if words is not None else \
                bloom_words_for_budget(n, m, storage_budget)
            self.k = 0
        else:
            self.words = 0
            self.k = int(k) if k is not None else \
                minhash_k_for_budget(n, m, storage_budget)
        self.dirty = np.zeros(n, dtype=bool)
        self.stale = np.zeros(n, dtype=np.int64)
        self.rows_rebuilt = 0
        self.rows_incremental = 0
        self.deltas_applied = 0
        if data is None:
            # build from the device mirror when it exists (StreamSession
            # creates it first — no second adjacency upload); otherwise the
            # meter copies before upload: jnp.asarray of a host buffer can
            # be zero-copy on CPU, and dyn.adj is mutated in place by
            # subsequent deltas while this build may still be executing
            # asynchronously
            adj_dev = (dyn._device.adj if dyn._device is not None
                       else dyn.traffic.put(dyn.adj, init=True))
            data = self._build_rows(adj_dev)
        self.sketch = SketchSet(
            data=data, kind=kind,
            num_hashes=self.num_hashes if kind == "bf" else 0,
            k=self.k, seed=self.seed, n=n)

    # -- full/selective construction through the chunked builders ----------

    def _row_fn(self):
        n = self.dyn.n
        if self.kind == "bf":
            return functools.partial(bloom_rows, n=n, words=self.words,
                                     num_hashes=self.num_hashes,
                                     seed=self.seed)
        fn = {"kh": khash_rows, "1h": onehash_rows, "kmv": kmv_rows}[self.kind]
        return functools.partial(fn, n=n, k=self.k, seed=self.seed)

    def _build_rows(self, adj_rows: jnp.ndarray) -> jnp.ndarray:
        if self.kind != "bf" and adj_rows.shape[1] < self.k:
            # keep-k row builders need at least k columns to slice
            adj_rows = jnp.pad(adj_rows,
                               ((0, 0), (0, self.k - adj_rows.shape[1])),
                               constant_values=self.dyn.n)
        tail = (self.words,) if self.kind == "bf" else (self.k,)
        dtype = {"bf": jnp.uint32, "kmv": jnp.float32}.get(self.kind, jnp.int32)
        return _map_vertex_chunks(self._row_fn(), adj_rows, self.chunk,
                                  tail, dtype)

    # -- delta application -------------------------------------------------

    def apply(self, delta: DeltaResult) -> np.ndarray:
        """Absorb one delta; returns the vertex ids rebuilt *now* (per the
        error-budget policy — empty when all deletions stayed affordable)."""
        self.deltas_applied += 1
        verts, new_nbrs = delta.insert_rows(self.dyn.n)
        if verts.size:
            with trace.span("sketch.insert", kind=self.kind,
                            rows=int(verts.size)) as sp:
                self._insert(verts, new_nbrs)
                sp.fence(self.sketch.data)
            self.rows_incremental += int(verts.size)
        if delta.deleted.size:
            ends = delta.deleted.ravel()
            self.dirty[delta.dirty] = True
            self.stale += np.bincount(ends, minlength=self.dyn.n)
        dirty_ids = np.nonzero(self.dirty)[0]
        if dirty_ids.size == 0:
            return dirty_ids
        allowed = self.policy.allowed_stale(self.sketch,
                                            self.dyn.deg[dirty_ids])
        rebuild = dirty_ids[self.stale[dirty_ids] > allowed]
        self.rebuild_rows(rebuild)
        return rebuild

    def _insert(self, verts: np.ndarray, new_nbrs: np.ndarray):
        # pad both axes to powers of two so jit recompiles stay bounded;
        # padded entries carry the out-of-range row index n and are dropped
        # by the scatter (a colliding in-range pad index could clobber a
        # real row's update)
        t, width = new_nbrs.shape
        t_p, l_p = pow2_bucket(t), pow2_bucket(width)
        rows = np.full(t_p, self.dyn.n, dtype=np.int32)
        rows[:t] = verts
        padded = np.full((t_p, l_p), self.dyn.n, dtype=np.int32)
        padded[:t, :width] = new_nbrs
        rows_j = self.dyn.traffic.put(rows)
        new_j = self.dyn.traffic.put(padded)
        if self.kind == "bf":
            data = _bloom_insert(self.sketch.data, rows_j, new_j,
                                 n=self.dyn.n, num_hashes=self.num_hashes,
                                 seed=self.seed,
                                 total_bits=self.sketch.total_bits)
        elif self.kind == "kh":
            data = _khash_insert(self.sketch.data, rows_j, new_j,
                                 n=self.dyn.n, seed=self.seed)
        elif self.kind == "1h":
            data = _onehash_insert(self.sketch.data, rows_j, new_j,
                                   n=self.dyn.n, seed=self.seed)
        else:
            data = _kmv_insert(self.sketch.data, rows_j, new_j,
                               n=self.dyn.n, seed=self.seed)
        self.sketch = dataclasses.replace(self.sketch, data=data)

    def rebuild_rows(self, verts: np.ndarray):
        """Selectively rebuild the given rows from the current adjacency
        through the chunked batch builders (never the full O(b·Σd_v) pass)."""
        verts = np.asarray(verts, dtype=np.int64)
        if verts.size == 0:
            return
        with trace.span("sketch.rebuild", kind=self.kind,
                        rows=int(verts.size)) as sp:
            self._rebuild_rows(verts)
            sp.fence(self.sketch.data)

    def _rebuild_rows(self, verts: np.ndarray):
        # bucket the row count to a power of two so deltas of varying size
        # reuse one compiled builder per (bucket, adjacency-width) pair;
        # padded entries carry row index n and are dropped by the scatter
        n, t = self.dyn.n, int(verts.size)
        bucket = pow2_bucket(t)
        rows_idx = np.full(bucket, n, dtype=np.int32)
        rows_idx[:t] = verts
        dev = self.dyn._device
        if dev is not None:
            # device-resident graph: gather the rebuild inputs from the live
            # device adjacency — only the row *indices* cross the host
            # boundary (pad index n clips to a real row, whose result the
            # scatter then drops)
            idx_j = self.dyn.traffic.put(rows_idx)
            adj_rows = jnp.take(dev.adj, jnp.clip(idx_j, 0, max(n - 1, 0)),
                                axis=0)
        else:
            idx_j = jnp.asarray(rows_idx)
            adj_rows_np = np.full((bucket, self.dyn.capacity), n,
                                  dtype=np.int32)
            adj_rows_np[:t] = self.dyn.adj[verts]
            adj_rows = jnp.asarray(adj_rows_np)
        rows = self._build_rows(adj_rows)
        data = self.sketch.data.at[idx_j].set(rows, mode="drop")
        self.sketch = dataclasses.replace(self.sketch, data=data)
        self.dirty[verts] = False
        self.stale[verts] = 0
        self.rows_rebuilt += int(verts.size)

    def flush(self) -> np.ndarray:
        """Force-rebuild every dirty row (e.g. before a checkpoint); returns
        the rebuilt vertex ids."""
        dirty_ids = np.nonzero(self.dirty)[0]
        self.rebuild_rows(dirty_ids)
        return dirty_ids

    def stats(self) -> dict:
        """Maintenance counters: incremental rows, rebuilds, staleness."""
        return {
            "kind": self.kind,
            "rows_incremental": self.rows_incremental,
            "rows_rebuilt": self.rows_rebuilt,
            "rows_dirty": int(self.dirty.sum()),
            "stale_total": int(self.stale.sum()),
            "deltas_applied": self.deltas_applied,
        }
