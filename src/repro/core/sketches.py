"""Probabilistic set representations of vertex neighborhoods (ProbGraph §II-D).

Every builder returns fixed-size per-vertex sketch arrays — the fixed size is
the point: it turns skewed set algebra into perfectly regular, shardable
tensor ops (paper Fig. 1, panel 5). The Bloom build reads the edge list; the
k-Hash, 1-Hash and KMV builders are pure functions of the padded adjacency.

Representations:
  * Bloom filter  : uint32[n, words]  (B = 32*words bits, b hash functions)
  * k-Hash MinHash: int32 [n, k]      (argmin element per hash function)
  * 1-Hash MinHash: int32 [n, k]      (elements with k smallest hashes, sorted
                                       by hash; sentinel-padded)
  * KMV           : float32[n, k]     (k smallest hash values in (0,1];
                                       pad = 2.0)

Sentinel for missing elements is ``n`` (== number of vertices), which can
never be a real vertex id.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace
from .graph import Graph
from .hashing import hash_u32, hash_unit_interval, np_hash_u32

PAD_HASH = np.uint32(0xFFFFFFFF)
KMV_PAD = np.float32(2.0)


# ----------------------------------------------------------------------------
# Storage-budget parameterization (paper §V-A)
# ----------------------------------------------------------------------------

def bloom_words_for_budget(n: int, m: int, s: float, min_words: int = 2) -> int:
    """Bloom words/vertex so total sketch bits ≈ s × CSR bits (CSR ≈ (2m+n)·32)."""
    csr_bits = (2 * m + n + 1) * 32
    bits_per_vertex = max(1.0, s * csr_bits / max(n, 1))
    words = int(np.ceil(bits_per_vertex / 32.0))
    # round UP to a multiple of 2 words (64-bit lanes) for vectorization;
    # clamping to min_words happens first so an odd min_words cannot leak an
    # odd word count through
    words = max(words, min_words)
    words += words % 2
    return words


def minhash_k_for_budget(n: int, m: int, s: float, min_k: int = 4) -> int:
    """k so total MinHash storage ≈ s × CSR storage (Wk bits per vertex)."""
    csr_words = 2 * m + n + 1
    k = int(np.floor(s * csr_words / max(n, 1)))
    return max(min_k, k)


# ----------------------------------------------------------------------------
# Bloom filters
# ----------------------------------------------------------------------------

def bloom_bits(adj_rows: jax.Array, n: int, num_hashes: int, total_bits: int,
               seed) -> jax.Array:
    """bool[rows, total_bits]: the bits the valid elements of each padded
    row (pad value == n) set.

    Positions are laid out ``[b, rows, d_max]``, the hash axis first: on a
    TPU a trailing axis of b elements is padded to a 128-lane tile, 64x the
    bytes at b = 2 (20 GB for 4096 rows of a scale-16 Graph500 graph).
    Duplicate positions are benign: the scatter takes the max (an OR).
    """
    valid = adj_rows < n
    safe = jnp.where(valid, adj_rows, 0)
    seeds = jnp.arange(num_hashes, dtype=jnp.uint32) + jnp.uint32(seed) * jnp.uint32(0x9E3779B9)
    pos = (hash_u32(safe[None], seeds[:, None, None])
           % jnp.uint32(total_bits)).astype(jnp.int32)   # [b, rows, d_max]
    vmask = jnp.broadcast_to(valid[None], pos.shape)
    row_idx = jnp.broadcast_to(
        jnp.arange(adj_rows.shape[0])[None, :, None], pos.shape)
    bits = jnp.zeros((adj_rows.shape[0], total_bits), dtype=jnp.bool_)
    return bits.at[row_idx.reshape(-1),
                   jnp.where(vmask, pos, 0).reshape(-1)].max(vmask.reshape(-1))


def bloom_rows(adj_rows: jax.Array, n: int, words: int, num_hashes: int = 2,
               seed: int = 0) -> jax.Array:
    """Bloom rows for a block of padded adjacency rows (pad value == n).

    Streaming maintenance rebuilds dirty rows through it; each row equals
    :func:`build_bloom`'s for the same neighbours (results are independent
    of the rows' padded width).
    """
    return pack_bits(bloom_bits(adj_rows, n, num_hashes, words * 32, seed))


def build_bloom(graph: Graph, words: int, num_hashes: int = 2,
                seed: int = 0) -> jax.Array:
    """Pure-JAX Bloom construction from the edge list: uint32[n, words].

    Each of the b·2m (row, neighbour) entries of ``graph.edges``, taken in
    both directions, sets one bit per hash function; ``graph.adj`` and its
    padding are never read. Work O(b·Σd_v) = O(b·2m), depth O(log(b·m))
    (paper Table V). Rows are bit-identical to :func:`bloom_rows` over the
    padded adjacency: an OR depends on neither order nor multiplicity.
    """
    n = graph.n
    return _bloom_from_edges(graph.edges, n=n, words=words,
                             num_hashes=num_hashes, seed=seed,
                             one_key=n * words * 32 < 2 ** 31)


@functools.partial(jax.jit, static_argnames=("n", "words", "num_hashes",
                                             "seed", "one_key"))
def _bloom_from_edges(edges: jax.Array, n: int, words: int, num_hashes: int,
                      seed: int, one_key: bool) -> jax.Array:
    """Sort the (row, bit position) pairs, keep the first of each run of
    equals, and add each kept bit into its word: with no bit twice in a
    word, the add is an OR. ``one_key`` sorts the int32 key ``row·32W +
    pos`` (it needs n·32W < 2**31), else the pair sorts lexicographically."""
    total_bits = words * 32
    src = jnp.concatenate([edges[:, 0], edges[:, 1]])
    dst = jnp.concatenate([edges[:, 1], edges[:, 0]])
    seeds = jnp.arange(num_hashes, dtype=jnp.uint32) + jnp.uint32(seed) * jnp.uint32(0x9E3779B9)
    pos = (hash_u32(dst[None], seeds[:, None])
           % jnp.uint32(total_bits)).astype(jnp.int32).reshape(-1)  # [b·2m]
    rows = jnp.tile(src, num_hashes)
    if one_key:
        key = jnp.sort(rows * total_bits + pos)
        new, word, bit = _run_starts(key), key >> 5, key & 31
    else:
        rows, pos = jax.lax.sort((rows, pos), num_keys=2)
        new = _run_starts(rows) | _run_starts(pos)
        word, bit = rows * words + (pos >> 5), pos & 31
    ones = jnp.where(new, jnp.uint32(1) << bit.astype(jnp.uint32),
                     jnp.uint32(0))
    out = jnp.zeros(n * words, jnp.uint32).at[word].add(
        ones, indices_are_sorted=True)
    return out.reshape(n, words)


def _run_starts(x: jax.Array) -> jax.Array:
    """bool mask of the entries of a 1-D array that differ from the one
    before (the first entry always)."""
    return jnp.concatenate([jnp.ones(min(1, x.shape[0]), jnp.bool_),
                            x[1:] != x[:-1]])


def pack_bits(bits: jax.Array) -> jax.Array:
    """bool[..., 32*w] -> uint32[..., w]."""
    *lead, total = bits.shape
    w = total // 32
    b32 = bits.reshape(*lead, w, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b32 << shifts, axis=-1).astype(jnp.uint32)


def unpack_bits(wordsarr: jax.Array) -> jax.Array:
    """uint32[..., w] -> bool[..., 32*w]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (wordsarr[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*wordsarr.shape[:-1], wordsarr.shape[-1] * 32).astype(jnp.bool_)


def build_bloom_np(graph: Graph, words: int, num_hashes: int = 2, seed: int = 0) -> np.ndarray:
    """Fast host-side construction with np.bitwise_or.at (one-shot builds)."""
    n = graph.n
    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    deg = np.diff(indptr)
    rows = np.repeat(np.arange(n), deg)
    total_bits = words * 32
    out = np.zeros((n, words), dtype=np.uint32)
    golden = 0x9E3779B9
    for i in range(num_hashes):
        s = np.uint32((i + seed * golden) & 0xFFFFFFFF)
        pos = np_hash_u32(indices, int(s)) % total_bits
        np.bitwise_or.at(out, (rows, pos >> 5), np.uint32(1) << (pos & 31))
    return out


def bloom_membership(bloom_row: jax.Array, candidates: jax.Array, n: int,
                     num_hashes: int, total_bits: int, seed: int = 0) -> jax.Array:
    """Query x ∈ X for a batch of candidates against one Bloom row.

    bloom_row: uint32[words]; candidates: int32[...]; returns bool[...].
    """
    valid = candidates < n
    safe = jnp.where(valid, candidates, 0)
    seeds = jnp.arange(num_hashes, dtype=jnp.uint32) + jnp.uint32(seed) * jnp.uint32(0x9E3779B9)
    h = hash_u32(safe[..., None], seeds)
    pos = (h % jnp.uint32(total_bits)).astype(jnp.int32)
    word = pos >> 5
    bit = (pos & 31).astype(jnp.uint32)
    got = (bloom_row[word] >> bit) & jnp.uint32(1)
    return jnp.all(got == 1, axis=-1) & valid


# ----------------------------------------------------------------------------
# MinHash (k-Hash): one argmin per hash function (multiset semantics)
# ----------------------------------------------------------------------------

def khash_rows(adj_rows: jax.Array, n: int, k: int, seed: int = 0) -> jax.Array:
    """k-Hash rows for a block of padded adjacency rows (pad value == n)."""
    valid = adj_rows < n
    safe = jnp.where(valid, adj_rows, 0)
    seeds = jnp.arange(k, dtype=jnp.uint32) + jnp.uint32(seed) * jnp.uint32(0x9E3779B9)
    h = hash_u32(safe[..., None], seeds)               # [rows, d_max, k]
    h = jnp.where(valid[..., None], h, PAD_HASH)
    arg = jnp.argmin(h, axis=1)                         # [rows, k]
    elems = jnp.take_along_axis(adj_rows, arg, axis=1)  # may pick pad if empty
    any_valid = jnp.any(valid, axis=1, keepdims=True)
    return jnp.where(any_valid, elems, n).astype(jnp.int32)


def build_khash(graph: Graph, k: int, seed: int = 0, chunk: int = 4096) -> jax.Array:
    """int32[n, k]: element with the smallest h_i among N_v, per hash fn i.

    Empty neighborhoods yield the sentinel ``n``. Work O(k·Σd_v),
    depth O(log d) (paper Table V).
    """
    fn = functools.partial(khash_rows, n=graph.n, k=k, seed=seed)
    return _map_vertex_chunks(fn, graph.adj, chunk, (k,), jnp.int32)


# ----------------------------------------------------------------------------
# MinHash (1-Hash): k smallest under a single hash function, sorted by hash
# ----------------------------------------------------------------------------

def onehash_rows(adj_rows: jax.Array, n: int, k: int, seed: int = 0) -> jax.Array:
    """1-Hash rows for a block of padded adjacency rows (pad value == n).

    Requires rows sorted ascending (pads last) so the stable argsort breaks
    hash ties by element id — the invariant both `Graph.adj` and the
    streaming `DynamicGraph` maintain.
    """
    valid = adj_rows < n
    safe = jnp.where(valid, adj_rows, 0)
    h = hash_u32(safe, jnp.uint32(seed))
    h = jnp.where(valid, h, PAD_HASH)
    order = jnp.argsort(h, axis=1)[:, :k]
    elems = jnp.take_along_axis(adj_rows, order, axis=1)
    hsel = jnp.take_along_axis(h, order, axis=1)
    return jnp.where(hsel == PAD_HASH, n, elems).astype(jnp.int32)


def build_1hash(graph: Graph, k: int, seed: int = 0, chunk: int = 4096) -> jax.Array:
    """int32[n, k]: elements with the k smallest h(x), ascending by hash.

    Rows with d_v < k are sentinel-padded. Work O(Σd_v), depth O(log d).
    """
    fn = functools.partial(onehash_rows, n=graph.n, k=k, seed=seed)
    return _map_vertex_chunks(fn, graph.adj, chunk, (k,), jnp.int32)


def onehash_values(sketch: jax.Array, n: int, seed: int = 0) -> jax.Array:
    """Recompute hash values of a 1-Hash sketch (uint32; pads -> 0xFFFFFFFF)."""
    valid = sketch < n
    h = hash_u32(jnp.where(valid, sketch, 0), jnp.uint32(seed))
    return jnp.where(valid, h, PAD_HASH)


# ----------------------------------------------------------------------------
# KMV: k smallest hash values mapped to (0, 1]  (paper §IX)
# ----------------------------------------------------------------------------

def kmv_rows(adj_rows: jax.Array, n: int, k: int, seed: int = 0) -> jax.Array:
    """KMV rows for a block of padded adjacency rows (pad value == n)."""
    valid = adj_rows < n
    safe = jnp.where(valid, adj_rows, 0)
    h = hash_unit_interval(safe, jnp.uint32(seed))
    h = jnp.where(valid, h, KMV_PAD)
    return jnp.sort(h, axis=1)[:, :k]


def build_kmv(graph: Graph, k: int, seed: int = 0, chunk: int = 4096) -> jax.Array:
    """float32[n, k]: k smallest unit-interval hashes, ascending; pad = 2.0."""
    fn = functools.partial(kmv_rows, n=graph.n, k=k, seed=seed)
    return _map_vertex_chunks(fn, graph.adj, chunk, (k,), jnp.float32)


# ----------------------------------------------------------------------------
# shared chunked-map driver
# ----------------------------------------------------------------------------

def _map_vertex_chunks(fn, adj: jax.Array, chunk: int, out_tail: Tuple[int, ...], dtype):
    n = adj.shape[0]
    if n <= chunk:
        return fn(adj)
    pad_rows = (-n) % chunk
    adj_p = jnp.pad(adj, ((0, pad_rows), (0, 0)), constant_values=n)
    blocks = adj_p.reshape(-1, chunk, adj.shape[1])
    out = jax.lax.map(fn, blocks)
    return out.reshape(-1, *out_tail)[:n].astype(dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SketchSet:
    """A named bundle of sketches for one graph (what `ProbGraph(g, ...)` is
    in the paper's Listing 6). Registered as a pytree (data = leaf) so it
    can be passed through jit as a runtime argument."""
    data: jax.Array             # per-vertex sketch matrix
    kind: str = dataclasses.field(metadata=dict(static=True))
    num_hashes: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))
    seed: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def total_bits(self) -> int:
        if self.kind == "bf":
            return self.data.shape[1] * 32
        return 0


def build(graph: Graph, kind: str, storage_budget: float = 0.25,
          num_hashes: int = 2, seed: int = 0, words: int | None = None,
          k: int | None = None) -> SketchSet:
    """Paper Listing 6 entry point: ProbGraph(g, KIND, s)."""
    if kind == "bf":
        w = words if words is not None else bloom_words_for_budget(graph.n, graph.m, storage_budget)
        with trace.span("sketch.bloom_build", words=w,
                        positions=num_hashes * 2 * graph.m):
            data = build_bloom(graph, w, num_hashes, seed)
        return SketchSet(data=data, kind="bf", num_hashes=num_hashes, k=0,
                         seed=seed, n=graph.n)
    kk = k if k is not None else minhash_k_for_budget(graph.n, graph.m, storage_budget)
    if kind in ("kh", "1h", "kmv"):
        builder = {"kh": build_khash, "1h": build_1hash, "kmv": build_kmv}[kind]
        return SketchSet(data=builder(graph, kk, seed), kind=kind,
                         num_hashes=0, k=kk, seed=seed, n=graph.n)
    raise ValueError(f"unknown sketch kind: {kind}")
