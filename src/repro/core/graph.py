"""Graph model: CSR + padded adjacency, generators, and host utilities.

The paper stores G in CSR (indptr + sorted neighbor arrays). For TPU/JAX we
additionally keep a *padded adjacency* matrix ``adj[n, d_max]`` (rows sorted,
padded with the sentinel ``n``) so that per-edge neighborhood gathers are a
single `jnp.take`, and vmapped set algebra (merge / galloping) is regular.

Degree skew makes the padded form wasteful for power-law graphs — exactly the
load-imbalance pathology the paper's fixed-size sketches remove — but it is
the right *exact-baseline* representation on a vector machine.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .hashing import np_hash_u32


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected graph in CSR + padded-adjacency form (device arrays).

    Attributes:
      indptr:  int32[n+1]   CSR row pointers.
      indices: int32[2m]    concatenated sorted neighbor lists.
      adj:     int32[n, d_max] padded adjacency (pad value == n).
      deg:     int32[n]     vertex degrees.
      edges:   int32[m, 2]  unique undirected edges with u < v.
      n_vertices / n_edges / d_max: static ints (aux data).
    """

    indptr: jax.Array
    indices: jax.Array
    adj: jax.Array
    deg: jax.Array
    edges: jax.Array
    n_vertices: int = dataclasses.field(metadata=dict(static=True))
    n_edges: int = dataclasses.field(metadata=dict(static=True))
    d_max: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.n_vertices

    @property
    def m(self) -> int:
        return self.n_edges


def canonical_edge_keys(n: int, edges) -> np.ndarray:
    """Sorted unique canonical keys ``lo·n + hi`` (u < v) of a raw edge array.

    Self loops and out-of-range endpoints are dropped; ``n == 0`` yields an
    empty key set (the key would otherwise divide by n on the way back out).
    Shared by :func:`from_edge_array` and the streaming ``DynamicGraph`` so
    both agree on edge identity.
    """
    if edges is None:
        return np.zeros(0, dtype=np.int64)
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if e.size == 0 or n == 0:
        return np.zeros(0, dtype=np.int64)
    u, v = e[:, 0], e[:, 1]
    keep = (u != v) & (u >= 0) & (v >= 0) & (u < n) & (v < n)
    u, v = u[keep], v[keep]
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    return np.unique(lo * n + hi)


def from_edge_array(n: int, edges: np.ndarray, pad_to_max_degree: Optional[int] = None) -> Graph:
    """Build a Graph from an (possibly duplicated / both-direction) edge array."""
    key = canonical_edge_keys(n, edges)
    if n > 0:
        lo, hi = key // n, key % n
    else:
        lo = hi = np.zeros(0, dtype=np.int64)
    m = lo.shape[0]

    # symmetric CSR
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    deg = np.bincount(src, minlength=n).astype(np.int32)
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(deg, out=indptr[1:])
    d_max = int(deg.max()) if n else 0
    if pad_to_max_degree is not None:
        d_max = max(d_max, pad_to_max_degree)
    d_max = max(d_max, 1)

    # padded adjacency, pad sentinel = n (sorts after every valid id)
    adj = np.full((n, d_max), n, dtype=np.int32)
    col = np.arange(len(src)) - indptr[src]
    adj[src, col] = dst

    return Graph(
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(dst.astype(np.int32)),
        adj=jnp.asarray(adj),
        deg=jnp.asarray(deg),
        edges=jnp.asarray(np.stack([lo, hi], axis=1).astype(np.int32)),
        n_vertices=int(n),
        n_edges=int(m),
        d_max=int(d_max),
    )


#: longest axis one cumulative op of :func:`running` spans
_SCAN_WIDTH = 1024


def running(x: jax.Array, op: str = "sum") -> jax.Array:
    """Inclusive running ``"sum"`` or ``"max"`` of a 1-D integer array.

    Scans rows of at most 1,024 entries and then, recursively, the row
    totals: the TPU compiler takes tens of seconds over one cumulative op
    on 2**20 entries, and under a second over [1024, 1024] rows.
    """
    cum, comb = ((jnp.cumsum, jnp.add) if op == "sum"
                 else (jax.lax.cummax, jnp.maximum))
    x = x.astype(jnp.int32)
    length = x.shape[0]
    if length <= _SCAN_WIDTH:
        return cum(x, axis=0)
    fill = 0 if op == "sum" else jnp.iinfo(jnp.int32).min
    rows = -(-length // _SCAN_WIDTH)
    inner = cum(jnp.concatenate(
        [x, jnp.full(rows * _SCAN_WIDTH - length, fill, jnp.int32)]
    ).reshape(rows, _SCAN_WIDTH), axis=1)
    before = jnp.concatenate([jnp.full(1, fill, jnp.int32),
                              running(inner[:, -1], op)[:-1]])
    return comb(inner, before[:, None]).reshape(-1)[:length]


@jax.jit
def degree_oriented_csr(indptr: jax.Array, indices: jax.Array):
    """Orient every edge from its lower to its higher (degree, id) rank.

    Built from the symmetric CSR alone (``adj`` is never read): entry j of
    ``indices`` is kept when its neighbour outranks its row, and the kept
    entries are compacted in order, so each oriented row stays sorted by
    neighbour id. Returns ``(oindptr int32[n+1], osrc int32[m], odst
    int32[m])``: oriented edge e runs osrc[e] -> odst[e], and row v is
    ``odst[oindptr[v]:oindptr[v+1]]`` (N⁺(v)).

    In this order an out-degree k needs k out-neighbours of degree at least
    k, so no row is longer than ⌊√(2m)⌋ (Chiba and Nishizeki).
    """
    n = indptr.shape[0] - 1
    m = indices.shape[0] // 2
    deg = indptr[1:] - indptr[:-1]
    # the row of each CSR entry: +1 at every row start, then a running sum
    src = running(jnp.zeros(2 * m, jnp.int32).at[indptr[1:-1]].add(
        1, mode="drop"))
    d_src, d_dst = jnp.take(deg, src), jnp.take(deg, indices)
    # (degree, id) rank compared directly: no sort
    keep = (d_dst > d_src) | ((d_dst == d_src) & (indices > src))
    slot = jnp.where(keep, running(keep) - 1, m)
    osrc = jnp.zeros(m, jnp.int32).at[slot].set(src, mode="drop")
    odst = jnp.zeros(m, jnp.int32).at[slot].set(indices, mode="drop")
    outdeg = jnp.zeros(n, jnp.int32).at[src].add(keep.astype(jnp.int32))
    oindptr = jnp.concatenate([jnp.zeros(1, jnp.int32), running(outdeg)])
    return oindptr, osrc, odst


def graph_view(n: int, m: int, deg: jax.Array, adj: jax.Array,
               edges: jax.Array) -> Graph:
    """``Graph`` over live device buffers — zero host → device traffic.

    The streaming hot path hands in its persistent device arrays (``deg``
    int32[n], ``adj`` int32[n, cap] sorted rows padded with n, ``edges``
    int32[m, 2] in canonical key order) and gets the engine's graph type
    without any host materialization: the CSR fields are *derived on device*
    — indptr is a cumsum of deg, and indices come from lexsorting both
    directions of the edge list by (src, dst), exactly how
    ``from_edge_array`` builds them, so the cost is O(m log m) (not O(n·cap)
    like a dense adjacency scan) with the sort shape pow2-bucketed to keep
    one compiled variant per size class across deltas. The only difference
    from ``from_edge_array`` is the adjacency width — ``cap`` headroom
    columns instead of a tight d_max — and the padding sentinel makes the
    extra columns invisible to every consumer.

    The CSR derivation is eager even though the streaming tc/lcc/similarity
    hot path reads only adj/deg/edges: ``Graph`` is a frozen pytree whose
    fields must be arrays (a lazy thunk would break flattening), and a view
    missing its CSR would fail *silently* in host-side consumers
    (``neighbors_np``, ``build_bloom_np``). The cost is device-only compute
    — zero host traffic, the resource this path actually bounds.
    """
    indptr = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(deg, dtype=jnp.int32)])
    cap = int(adj.shape[1]) if n else 1
    m_b = 1 << (max(2 * m, 1) - 1).bit_length()
    pad = jnp.full(m_b - 2 * m, n, dtype=jnp.int32)    # sorts after real ids
    src = jnp.concatenate([edges[:, 0], edges[:, 1], pad])
    dst = jnp.concatenate([edges[:, 1], edges[:, 0], pad])
    order = jnp.lexsort((dst, src))[: 2 * m]
    indices = jnp.take(dst, order).astype(jnp.int32)
    return Graph(indptr=indptr, indices=indices, adj=adj, deg=deg,
                 edges=edges, n_vertices=int(n), n_edges=int(m),
                 d_max=max(cap, 1))


# ----------------------------------------------------------------------------
# Generators (paper: Kronecker power-law synthetics + real-world sets)
# ----------------------------------------------------------------------------

def erdos_renyi(n: int, p: float, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    max_pairs = n * (n - 1) // 2
    if max_pairs == 0 or p <= 0.0:
        return from_edge_array(n, np.zeros((0, 2), dtype=np.int64))
    if max_pairs <= 4_000_000:
        iu = np.triu_indices(n, k=1)
        mask = rng.random(iu[0].shape[0]) < p
        edges = np.stack([iu[0][mask], iu[1][mask]], axis=1)
    else:
        # geometric skipping over the linearized upper triangle: each slot is
        # kept independently with prob p by jumping Geometric(p) positions at
        # a time — the exact Bernoulli process, so no duplicate pairs, no
        # self loops, and E[m] = p·max_pairs, without n² memory on big n.
        sel = []
        pos = np.int64(-1)
        batch = int(1.2 * p * max_pairs) + 1024
        while pos < max_pairs:
            gaps = rng.geometric(p, size=batch).astype(np.int64)
            steps = np.cumsum(gaps) + pos
            sel.append(steps[steps < max_pairs])
            pos = steps[-1]
        t = np.concatenate(sel)
        edges = np.stack(_triu_unrank(t, n), axis=1)
    return from_edge_array(n, edges)


def _triu_unrank(t: np.ndarray, n: int):
    """Linear index t in the row-major strict upper triangle -> (u, v), u < v.

    Row u starts at S(u) = u·(2n-1-u)/2; invert via the float quadratic root,
    then correct the rare off-by-one from sqrt rounding.
    """
    u = np.floor((2.0 * n - 1.0 - np.sqrt((2.0 * n - 1.0) ** 2 - 8.0 * t)) / 2.0
                 ).astype(np.int64)
    for _ in range(2):
        start = u * (2 * n - 1 - u) // 2
        u = np.where(start > t, u - 1, u)
        end = (u + 1) * (2 * n - 2 - u) // 2
        u = np.where(end <= t, u + 1, u)
    v = t - u * (2 * n - 1 - u) // 2 + u + 1
    return u, v


def kronecker(scale: int, edge_factor: int = 16, seed: int = 0,
              a: float = 0.57, b: float = 0.19, c: float = 0.19) -> Graph:
    """Graph500-style stochastic Kronecker (power-law degree distribution)."""
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for bit in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        src_bit = r1 > ab
        thresh = np.where(src_bit, c / (1.0 - ab), a / ab)
        dst_bit = r2 > thresh
        src += src_bit.astype(np.int64) << bit
        dst += dst_bit.astype(np.int64) << bit
    # permute vertex ids to destroy locality (standard practice)
    perm = rng.permutation(n)
    return from_edge_array(n, np.stack([perm[src], perm[dst]], axis=1))


def barabasi_albert(n: int, m_attach: int = 4, seed: int = 0) -> Graph:
    """Preferential-attachment power-law graph (cheap host construction)."""
    rng = np.random.default_rng(seed)
    targets = list(range(m_attach))
    repeated: list[int] = []
    edges = []
    for v in range(m_attach, n):
        for t in targets:
            edges.append((v, t))
        repeated.extend(targets)
        repeated.extend([v] * m_attach)
        idx = rng.integers(0, len(repeated), size=m_attach)
        targets = [repeated[i] for i in idx]
    return from_edge_array(n, np.asarray(edges, dtype=np.int64))


def random_bipartite_community(n: int, communities: int, p_in: float, p_out: float,
                               seed: int = 0) -> Graph:
    """Planted-partition graph: dense communities, sparse cross edges."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, communities, size=n)
    u = rng.integers(0, n, size=int(6 * n * max(p_in, 1e-6) * n / communities) + 4 * n)
    v = rng.integers(0, n, size=u.shape[0])
    same = labels[u] == labels[v]
    keep = np.where(same, rng.random(u.shape[0]) < p_in, rng.random(u.shape[0]) < p_out)
    return from_edge_array(n, np.stack([u[keep], v[keep]], axis=1))


# ----------------------------------------------------------------------------
# Host helpers
# ----------------------------------------------------------------------------

def neighbors_np(g: Graph, v: int) -> np.ndarray:
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    return indices[indptr[v]:indptr[v + 1]]


def triangle_count_dense(g: Graph) -> int:
    """Exact TC oracle via dense A^3 trace (small graphs only)."""
    n = g.n
    a = np.zeros((n, n), dtype=np.int64)
    e = np.asarray(g.edges)
    a[e[:, 0], e[:, 1]] = 1
    a[e[:, 1], e[:, 0]] = 1
    return int(np.trace(a @ a @ a) // 6)


def four_clique_count_bruteforce(g: Graph) -> int:
    """Exact 4-clique oracle (tiny graphs only): O(m * d^2)."""
    n = g.n
    adj_sets = [set(neighbors_np(g, v).tolist()) for v in range(n)]
    count = 0
    e = np.asarray(g.edges)
    for u, v in e:
        common = sorted(adj_sets[u] & adj_sets[v])
        for i in range(len(common)):
            wi = common[i]
            for j in range(i + 1, len(common)):
                wj = common[j]
                if wj in adj_sets[wi]:
                    count += 1
    return count // 6  # each 4-clique counted once per each of its 6 edges
