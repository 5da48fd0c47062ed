"""Hash functions for probabilistic set representations.

The paper uses MurmurHash3 for its speed/simplicity; we use the murmur3
``fmix32`` finalizer (the avalanche core of MurmurHash3) on uint32 keys,
parameterized by a per-function seed. Pure jnp on uint32 so it is jit-able,
vmap-able, and bit-exact across hosts (important for distributed sketch
construction: every shard must agree on h_i(x)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)  # seed spacing (Weyl constant)


def fmix32(x: jax.Array) -> jax.Array:
    """Murmur3 32-bit finalizer. x: uint32 array -> uint32 array."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _C1
    x = x ^ (x >> 13)
    x = x * _C2
    x = x ^ (x >> 16)
    return x


def hash_u32(x: jax.Array, seed) -> jax.Array:
    """Seeded 32-bit hash of integer keys. Accepts any int dtype."""
    x = x.astype(jnp.uint32)
    seed = jnp.asarray(seed, dtype=jnp.uint32)
    return fmix32(x ^ fmix32(seed * _GOLDEN + jnp.uint32(1)))


def hash_unit_interval(x: jax.Array, seed) -> jax.Array:
    """Hash keys to (0, 1] as float32 (for KMV sketches)."""
    h = hash_u32(x, seed)
    # (h + 1) / 2^32 in (0, 1]; do it in float64-free fashion
    return (h.astype(jnp.float32) + 1.0) * jnp.float32(2.0 ** -32)


# numpy twin (bit-identical) for fast host-side construction ---------------

def np_fmix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = (x * _C1).astype(np.uint32)
        x = x ^ (x >> np.uint32(13))
        x = (x * _C2).astype(np.uint32)
        x = x ^ (x >> np.uint32(16))
    return x


def np_hash_u32(x: np.ndarray, seed: int) -> np.ndarray:
    x = np.asarray(x, dtype=np.uint32)
    seed = np.uint32(seed)
    with np.errstate(over="ignore"):
        inner = np_fmix32(np.asarray(seed * _GOLDEN + np.uint32(1), dtype=np.uint32))
    return np_fmix32(x ^ inner)
