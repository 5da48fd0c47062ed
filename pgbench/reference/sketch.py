"""Bloom-filter neighbourhood sketches and the AND estimator, in numpy.

Sketch semantics (ProbGraph §II-D, §IV): vertex v's row is a B-bit Bloom
filter (B = 32 · words) of its neighbour ids under b hash functions. Hash i
of id x is the MurmurHash3 32-bit finalizer applied to
``x XOR fmix32(s_i · 0x9E3779B9 + 1)`` with ``s_i = i + seed · 0x9E3779B9``
(mod 2**32); bit position ``h mod B`` lands in word ``pos // 32`` at bit
``pos % 32``. |X ∩ Y| is estimated from the ones of the AND of two rows by
the Swamidass estimator ``-(B / b) · ln(1 - ones / B)``, with ones capped at
B - 1.
"""
from __future__ import annotations

import numpy as np

GOLDEN = 0x9E3779B9
M32 = 0xFFFFFFFF


def fmix32(x: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer over uint64 values holding uint32s."""
    x = np.asarray(x, dtype=np.uint64) & M32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    x ^= x >> 16
    return x


def hash_ids(ids: np.ndarray, i: int, seed: int) -> np.ndarray:
    """Hash function ``i`` of the family with ``seed`` over vertex ids."""
    s = (i + seed * GOLDEN) & M32
    inner = int(fmix32(np.array([(s * GOLDEN + 1) & M32]))[0])
    return fmix32(np.asarray(ids, dtype=np.uint64) ^ np.uint64(inner))


def bit_positions(ids: np.ndarray, words: int, num_hashes: int,
                  seed: int) -> list:
    """Per hash function, the bit position of each id."""
    total = np.uint64(words * 32)
    return [hash_ids(ids, i, seed) % total for i in range(num_hashes)]


def bloom_rows(n: int, src: np.ndarray, dst: np.ndarray, words: int,
               num_hashes: int, seed: int) -> np.ndarray:
    """uint32[n, words]: row ``src[j]`` holds element ``dst[j]``."""
    out = np.zeros((n, words), dtype=np.uint32)
    for pos in bit_positions(dst, words, num_hashes, seed):
        np.bitwise_or.at(out, (src, (pos >> np.uint64(5)).astype(np.int64)),
                         (np.uint32(1) << (pos & np.uint64(31))
                          .astype(np.uint32)))
    return out


def bloom_of_graph(n: int, uv: np.ndarray, words: int, num_hashes: int,
                   seed: int) -> np.ndarray:
    """Neighbourhood sketches of the undirected graph with edges ``uv``."""
    src = np.concatenate([uv[:, 0], uv[:, 1]])
    dst = np.concatenate([uv[:, 1], uv[:, 0]])
    return bloom_rows(n, src, dst, words, num_hashes, seed)


_POP8 = np.array([bin(i).count("1") for i in range(256)], dtype=np.int64)


def popcount(rows: np.ndarray) -> np.ndarray:
    """Ones per row over the trailing uint32 axis."""
    b = np.ascontiguousarray(rows).view(np.uint8)
    return _POP8[b].sum(axis=-1)


def and_ones(sketch: np.ndarray, u: np.ndarray, v: np.ndarray,
             chunk: int = 1 << 16) -> np.ndarray:
    """int64 popcount of ``sketch[u] & sketch[v]`` per pair, in chunks."""
    out = np.empty(u.shape[0], dtype=np.int64)
    for lo in range(0, u.shape[0], chunk):
        hi = lo + chunk
        out[lo:hi] = popcount(sketch[u[lo:hi]] & sketch[v[lo:hi]])
    return out


def and_estimate(ones: np.ndarray, total_bits: int, num_hashes: int,
                 dtype=np.float64) -> np.ndarray:
    """Swamidass |X ∩ Y| estimate from AND ones, computed in ``dtype``."""
    b = np.asarray(total_bits, dtype)
    o = np.minimum(ones, total_bits - 1).astype(dtype)
    return (-(b / np.asarray(num_hashes, dtype))
            * np.log1p(-(o / b).astype(dtype)).astype(dtype)).astype(dtype)
