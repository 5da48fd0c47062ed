"""ProbGraph estimators of |X|, |X∩Y| and Jaccard (paper §IV, §IX, App. E/G).

Every function is a pure, batched jnp op over *rows* of sketch matrices, so it
vmaps/shards trivially: inputs are `[..., words]` (BF), `[..., k]` (MH/KMV).
Heavy BF paths can be routed through the Pallas kernels (see repro.kernels.ops);
these jnp forms are the reference semantics used by tests.

Notation maps to the paper:  B = total bits, b = #hash functions,
ones = B_{X∩Y,1}, k = sketch size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .sketches import PAD_HASH, KMV_PAD


def _popcount_words(w: jax.Array) -> jax.Array:
    return jnp.sum(jax.lax.population_count(w), axis=-1).astype(jnp.int32)


# ----------------------------------------------------------------------------
# Bloom filters
# ----------------------------------------------------------------------------

def bf_size_swamidass(row: jax.Array, num_hashes: int) -> jax.Array:
    """|X|_S (Eq. 1), with the divergence fix of App. C-3 (ones==B -> ones-1)."""
    total_bits = row.shape[-1] * 32
    ones = _popcount_words(row).astype(jnp.float32)
    ones = jnp.where(ones >= total_bits, total_bits - 1, ones)
    return -(total_bits / num_hashes) * jnp.log1p(-ones / total_bits)


def bf_intersection_and(row_x: jax.Array, row_y: jax.Array, num_hashes: int) -> jax.Array:
    """|X∩Y|_AND (Eq. 2): Swamidass estimator on Bx AND By."""
    return bf_size_swamidass(row_x & row_y, num_hashes)


def bf_intersection_and_from_ones(ones: jax.Array, total_bits: int, num_hashes: int) -> jax.Array:
    """Eq. 2 given a precomputed popcount (e.g. from the Pallas kernel)."""
    ones = jnp.minimum(ones.astype(jnp.float32), total_bits - 1)
    return -(total_bits / num_hashes) * jnp.log1p(-ones / total_bits)


def bf_intersection_or(row_x: jax.Array, row_y: jax.Array, num_hashes: int,
                       size_x: jax.Array, size_y: jax.Array) -> jax.Array:
    """|X∩Y|_OR (Eq. 29, Swamidass prior work): |X|+|Y| - |X∪Y|_S via OR."""
    union_est = bf_size_swamidass(row_x | row_y, num_hashes)
    return size_x.astype(jnp.float32) + size_y.astype(jnp.float32) - union_est


# ----------------------------------------------------------------------------
# k-Hash MinHash (Eq. 5)
# ----------------------------------------------------------------------------

def khash_jaccard(mx: jax.Array, my: jax.Array, n: int) -> jax.Array:
    """Ĵ_kH = |M_X ∩ M_Y| / k with multiset (per-hash-function) alignment."""
    k = mx.shape[-1]
    both_valid = (mx < n) & (my < n)
    matches = jnp.sum((mx == my) & both_valid, axis=-1)
    return matches.astype(jnp.float32) / k


def minhash_intersection(j_hat: jax.Array, size_x: jax.Array, size_y: jax.Array) -> jax.Array:
    """|X∩Y| = Ĵ/(1+Ĵ) · (|X|+|Y|)  (Eq. 5 and the 1-Hash analogue)."""
    s = size_x.astype(jnp.float32) + size_y.astype(jnp.float32)
    return j_hat / (1.0 + j_hat) * s


def khash_intersection(mx: jax.Array, my: jax.Array, size_x, size_y, n: int) -> jax.Array:
    return minhash_intersection(khash_jaccard(mx, my, n), size_x, size_y)


# ----------------------------------------------------------------------------
# 1-Hash MinHash (paper §IV-D)
# ----------------------------------------------------------------------------

def _sorted_intersect_count(a: jax.Array, b: jax.Array, sentinel: int) -> jax.Array:
    """|set(a) ∩ set(b)| for sentinel-padded, duplicate-free rows.

    O(k²) dense compare — the TPU-friendly form of a sorted merge (DESIGN §2).
    """
    eq = a[..., :, None] == b[..., None, :]
    valid = (a[..., :, None] < sentinel) & (b[..., None, :] < sentinel)
    return jnp.sum(eq & valid, axis=(-2, -1)).astype(jnp.int32)


def onehash_jaccard_naive(mx: jax.Array, my: jax.Array, n: int) -> jax.Array:
    """Paper's literal Ĵ_1H = |M¹_X ∩ M¹_Y| / k."""
    k = mx.shape[-1]
    return _sorted_intersect_count(mx, my, n).astype(jnp.float32) / k


def onehash_jaccard_union(mx: jax.Array, my: jax.Array, hx: jax.Array, hy: jax.Array,
                          n: int) -> jax.Array:
    """Union-k-min Ĵ_1H: among the k smallest hashes of X∪Y (merged from the two
    sketches), the fraction present in both sketches.

    This matches the Hyper(|X∪Y|, |X∩Y|, k) sampling model assumed by
    Prop IV.3 (sampling w/o replacement from the union), and is the default.
    mx/my are 1-Hash sketches sorted by hash; hx/hy their uint32 hash values.
    """
    k = mx.shape[-1]
    # merge the two sorted-k lists, dedupe by element id, take k smallest
    elems = jnp.concatenate([mx, my], axis=-1)
    hsh = jnp.concatenate([hx, hy], axis=-1)
    # mark duplicates (same element in both sketches): keep one copy
    dup = _pairwise_dup_mask(mx, my, n)
    hsh = jnp.where(jnp.concatenate([jnp.zeros_like(mx, bool), dup], axis=-1), PAD_HASH, hsh)
    order = jnp.argsort(hsh, axis=-1)
    top_h = jnp.take_along_axis(hsh, order, axis=-1)[..., :k]
    top_e = jnp.take_along_axis(elems, order, axis=-1)[..., :k]
    top_e = jnp.where(top_h == PAD_HASH, n, top_e)
    in_x = _membership(top_e, mx, n)
    in_y = _membership(top_e, my, n)
    denom = jnp.maximum(jnp.sum(top_e < n, axis=-1), 1)
    return jnp.sum(in_x & in_y, axis=-1).astype(jnp.float32) / denom.astype(jnp.float32)


def _pairwise_dup_mask(mx: jax.Array, my: jax.Array, n: int) -> jax.Array:
    """For each element of my, is it also present in mx?"""
    eq = my[..., :, None] == mx[..., None, :]
    valid = (my[..., :, None] < n) & (mx[..., None, :] < n)
    return jnp.any(eq & valid, axis=-1)


def _membership(queries: jax.Array, table: jax.Array, n: int) -> jax.Array:
    eq = queries[..., :, None] == table[..., None, :]
    valid = (queries[..., :, None] < n) & (table[..., None, :] < n)
    return jnp.any(eq & valid, axis=-1)


def onehash_intersection(mx, my, hx, hy, size_x, size_y, n: int,
                         variant: str = "union") -> jax.Array:
    if variant == "naive":
        j = onehash_jaccard_naive(mx, my, n)
    else:
        j = onehash_jaccard_union(mx, my, hx, hy, n)
    return minhash_intersection(j, size_x, size_y)


# ----------------------------------------------------------------------------
# KMV (paper §IX, App. G)
# ----------------------------------------------------------------------------

def kmv_size(kmv_row: jax.Array) -> jax.Array:
    """|X|_K = (k-1)/max(K_X) (Eq. 39); handles partially-filled sketches."""
    filled = jnp.sum(kmv_row < KMV_PAD, axis=-1)
    kmax = jnp.max(jnp.where(kmv_row < KMV_PAD, kmv_row, 0.0), axis=-1)
    est = (filled.astype(jnp.float32) - 1.0) / jnp.maximum(kmax, 1e-20)
    # if the sketch isn't full, it IS the whole set: |X| = filled
    full = filled >= kmv_row.shape[-1]
    return jnp.where(full, est, filled.astype(jnp.float32))


def kmv_union_size(kx: jax.Array, ky: jax.Array) -> jax.Array:
    """|X∪Y|_K from the k smallest of K_X ∪ K_Y (dedup by hash value)."""
    k = kx.shape[-1]
    merged = jnp.concatenate([kx, ky], axis=-1)
    merged = jnp.sort(merged, axis=-1)
    # dedupe equal adjacent values (same element hashed in both sets)
    dup = jnp.concatenate(
        [jnp.zeros_like(merged[..., :1], bool), merged[..., 1:] == merged[..., :-1]],
        axis=-1) & (merged < KMV_PAD)
    merged = jnp.where(dup, KMV_PAD, merged)
    merged = jnp.sort(merged, axis=-1)[..., :k]
    return kmv_size(merged)


def kmv_intersection(kx: jax.Array, ky: jax.Array, size_x, size_y) -> jax.Array:
    """|X∩Y|_K = |X| + |Y| - |X∪Y|_K (Eq. 41, exact degrees known)."""
    union = kmv_union_size(kx, ky)
    est = size_x.astype(jnp.float32) + size_y.astype(jnp.float32) - union
    return jnp.maximum(est, 0.0)
