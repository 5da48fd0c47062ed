"""Mutable graph store for the streaming subsystem.

``DynamicGraph`` owns the same CSR + padded-adjacency representation as the
frozen :class:`repro.core.graph.Graph`, but host-side (numpy) and mutable:
adjacency rows carry *headroom* slots so a batched ``apply_delta`` usually
edits rows in place instead of reallocating. The host arrays stay the source
of truth; the serving hot path never re-uploads them. Instead a
:class:`DeviceGraphState` keeps ``deg``/``adj``/``edges`` resident on device
and ``apply_delta`` pushes only the touched rows — a jitted (donated off
CPU) scatter-update plus an edge-list splice sized by the delta — so host →
device traffic per delta is proportional to the delta, not to O(n·d_max+m).
``view()`` wraps the live device buffers in a lightweight ``Graph`` for the
engine; ``snapshot()`` is the *explicit* full host materialization, needed
only by ``save()`` / ``--verify`` style consumers, and is bit-identical to
``from_edge_array`` on the same edge set.

The vertex set [0, n) is fixed; edges arrive and depart in batches. Edge
identity is the canonical key ``lo·n + hi`` (u < v), kept as one sorted
int64 array so delta application and carry-index computation are pure
vectorized set algebra (SISA's framing: updates are set operations too).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
import weakref
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import Graph, canonical_edge_keys, graph_view
from ..engine.api import pow2_bucket
from ..obs import trace
from ..obs.metrics import MetricsRegistry


@dataclasses.dataclass(frozen=True)
class DeltaResult:
    """What one ``apply_delta`` actually changed (post-canonicalization).

    Attributes:
      inserted: int64[I, 2]  newly present edges (u < v).
      deleted:  int64[D, 2]  removed edges (u < v).
      touched:  int64[T]     sorted unique vertices with any adjacency change.
      dirty:    int64[Dv]    sorted unique vertices that *lost* a neighbor
                             (their sketches cannot be updated monotonically).
      version:  graph version after this delta.
    """

    inserted: np.ndarray
    deleted: np.ndarray
    touched: np.ndarray
    dirty: np.ndarray
    version: int

    @property
    def is_noop(self) -> bool:
        """True when the delta changed nothing (all edges already as asked)."""
        return self.inserted.size == 0 and self.deleted.size == 0

    def insert_rows(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """Per-vertex new-neighbor lists, padded for batched device updates.

        Returns ``(verts int32[T], new int32[T, L])`` where row i holds the
        neighbors vertex ``verts[i]`` gained, sorted ascending, padded with
        the sentinel ``n`` — the shape incremental sketch maintenance eats.
        """
        if self.inserted.size == 0:
            return (np.zeros(0, dtype=np.int32),
                    np.zeros((0, 1), dtype=np.int32))
        src = np.concatenate([self.inserted[:, 0], self.inserted[:, 1]])
        dst = np.concatenate([self.inserted[:, 1], self.inserted[:, 0]])
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
        verts, start = np.unique(src, return_index=True)
        counts = np.diff(np.append(start, src.size))
        # offset scatter: within-group column = global rank - group start
        row = np.repeat(np.arange(verts.size), counts)
        col = np.arange(src.size) - np.repeat(start, counts)
        padded = np.full((verts.size, int(counts.max())), n, dtype=np.int32)
        padded[row, col] = dst
        return verts.astype(np.int32), padded


class TrafficMeter:
    """Host → device upload accounting for the streaming delta path.

    ``put()`` is the single doorway every streaming upload goes through, so
    ``bytes_delta`` (reset by ``begin_delta``) is an *exact* measure of host
    traffic per delta — the quantity the device-resident design bounds by
    the delta size. Init-time puts copy the host buffer first: ``jnp.asarray``
    can be zero-copy on CPU and the session-open uploads pass ``dyn.deg`` /
    ``dyn.adj``, which later deltas mutate in place; delta-path callers all
    pass freshly built padded buffers, so they skip the copy.

    The numbers live in a :class:`~repro.obs.metrics.MetricsRegistry`
    (``traffic_bytes{path=init|delta}``, ``traffic_bytes_last_delta``,
    ``traffic_steps``); the historical attribute names (``bytes_init`` etc.)
    and the ``stats()`` dict are views over those instruments, shape- and
    value-identical to the pre-registry meter.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = MetricsRegistry() if registry is None else registry
        self._init = self.registry.counter("traffic_bytes", path="init")
        self._total = self.registry.counter("traffic_bytes", path="delta")
        self._last = self.registry.gauge("traffic_bytes_last_delta")
        self._steps = self.registry.counter("traffic_steps")
        self._donated = self.registry.counter("device_updates_donated")

    @property
    def bytes_init(self) -> int:
        """One-time device residency bytes (session open)."""
        return self._init.value

    @property
    def bytes_total(self) -> int:
        """Cumulative delta-path upload bytes."""
        return self._total.value

    @property
    def bytes_delta(self) -> int:
        """Upload bytes since the last ``begin_delta()``."""
        return int(self._last.value)

    @property
    def steps(self) -> int:
        """Committed delta/flush traffic steps."""
        return self._steps.value

    @property
    def donated(self) -> int:
        """Device updates that donated the published buffers."""
        return self._donated.value

    def count_donation(self):
        """Count one device update that donated its input buffers."""
        self._donated.inc()

    def begin_delta(self):
        """Reset the per-delta byte counter (called at each delta's start)."""
        self._last.set(0)

    def commit_step(self):
        """Count one real delta/flush step (no-op steps stay unmetered so
        ``bytes_per_delta_mean`` reflects deltas that did work)."""
        self._steps.inc()

    def put(self, arr: np.ndarray, init: bool = False) -> jax.Array:
        """Upload a host buffer, metering its bytes (init vs delta path)."""
        host = np.array(arr, copy=True) if init else np.ascontiguousarray(arr)
        if init:
            self._init.inc(host.nbytes)
        else:
            self._last.add(host.nbytes)
            self._total.inc(host.nbytes)
        return jnp.asarray(host)

    def stats(self) -> dict:
        """Upload accounting: init/total/last-delta bytes and step count."""
        return {
            "bytes_init": self.bytes_init,
            "bytes_total": self.bytes_total,
            "bytes_last_delta": self.bytes_delta,
            "bytes_per_delta_mean": self.bytes_total / max(self.steps, 1),
            "steps": self.steps,
            "donated_updates": self.donated,
        }


def _scatter_rows_impl(adj, verts, rows):
    """adj[verts] <- rows over the leading row columns; pad verts == n drop."""
    cols = jnp.arange(rows.shape[1], dtype=jnp.int32)
    return adj.at[verts[:, None], cols[None, :]].set(rows, mode="drop")


def _scatter_vals_impl(vec, verts, vals):
    return vec.at[verts].set(vals, mode="drop")


def _splice_edges_impl(edges, del_pos, ins_pos, ins_uv, m_old, n):
    """Delta-sized splice of the canonical-order device edge list.

    ``edges`` is int32[e_cap, 2]: valid edges in (lo, hi)-lex == key order at
    [0, m_old), sentinel rows (n, n) after. Deleted positions are sentineled,
    inserts land in the free slots [m_old, m_old+I), and one on-device
    lexsort restores canonical order (sentinels sort last) — zero host
    traffic beyond the delta-sized index/edge uploads. Also returns the
    position carry: ``carry[j]`` is new edge j's position in the *old* order
    (or -1 for an insert), the device-resident replacement for uploading an
    O(m) carry index into the session's cardinality-cache refresh.
    """
    e_cap = edges.shape[0]
    pos = jnp.arange(e_cap, dtype=jnp.int32)
    deleted = jnp.zeros(e_cap, jnp.bool_).at[del_pos].set(True, mode="drop")
    edges = jnp.where(deleted[:, None], jnp.int32(n), edges)
    edges = edges.at[ins_pos].set(ins_uv, mode="drop")
    order = jnp.lexsort((edges[:, 1], edges[:, 0])).astype(jnp.int32)
    new_edges = jnp.take(edges, order, axis=0)
    old_flag = (pos < m_old) & ~deleted
    carry = jnp.where(jnp.take(old_flag, order), order, jnp.int32(-1))
    return new_edges, carry


@functools.lru_cache(maxsize=None)
def _update_fns(donate: bool):
    """The jitted device-update kernels, donating their first argument
    when ``donate`` is set.

    Donating the old buffer gives true in-place device updates.
    ``donate=False`` selects non-donating variants whenever an in-flight
    flush still reads the published buffers, since donating them to build
    the next version would invalidate arrays under it.
    :meth:`DynamicGraph.donate_ok` makes the per-delta call — a session's
    lease-aware policy when one is installed (donation re-engages whenever
    no stale view is in flight and no read lease is out), else the
    conservative any-live-snapshot veto.
    """
    argnums = (0,) if donate else ()
    return tuple(jax.jit(fn, donate_argnums=argnums) for fn in
                 (_scatter_rows_impl, _scatter_vals_impl, _splice_edges_impl))


class _DeviceBuffers(NamedTuple):
    # one immutable generation of the device mirror: swapped wholesale at
    # the end of every delta so concurrent readers never see a half-applied
    # generation (deg from version N+1, edges still at N)
    deg: jax.Array
    adj: jax.Array
    edges: jax.Array
    e_cap: int
    m: int


class DeviceGraphState:
    """Persistent device mirrors of a DynamicGraph's deg/adj/edges.

    Created once per session (one full upload, metered as ``bytes_init``);
    afterwards every delta is absorbed by delta-sized scatter-updates with
    pow2-bucketed shapes, so a handful of compiled variants serve any delta
    and per-delta host traffic scales with the delta, never with n·d_max.
    Capacity growth (adjacency headroom exhausted, edge buffer full) happens
    *on device* via sentinel padding — still zero full-graph upload; the
    grown rows themselves arrive through the ordinary touched-row scatter.

    The mirror is **double-buffered**: ``deg``/``adj``/``edges`` read one
    immutable published generation, and :meth:`apply_delta` builds the next
    generation into shadow locals (jax arrays are persistent, so the shadow
    shares all unchanged device memory) before publishing it with a single
    atomic attribute swap. A reader that captured the published arrays —
    a ``view()`` graph pinned by an in-flight flush — keeps a consistent
    version-N world no matter how many deltas land meanwhile.
    """

    def __init__(self, dyn: "DynamicGraph", meter: TrafficMeter):
        self.n = dyn.n
        self.meter = meter
        e_cap = pow2_bucket(max(dyn.m, 1))
        edges = np.full((e_cap, 2), dyn.n, dtype=np.int32)
        edges[:dyn.m] = dyn.edge_array()
        self._buf = _DeviceBuffers(meter.put(dyn.deg, init=True),
                                   meter.put(dyn.adj, init=True),
                                   meter.put(edges, init=True), e_cap, dyn.m)
        self.last_carry: Optional[jax.Array] = None
        self._identity: Optional[jax.Array] = None

    @property
    def deg(self) -> jax.Array:
        """Published device degree vector int32[n]."""
        return self._buf.deg

    @property
    def adj(self) -> jax.Array:
        """Published device padded adjacency int32[n, cap]."""
        return self._buf.adj

    @property
    def edges(self) -> jax.Array:
        """Published device edge list int32[e_cap, 2] (sentinel-padded)."""
        return self._buf.edges

    @property
    def e_cap(self) -> int:
        """Published edge-buffer capacity."""
        return self._buf.e_cap

    @property
    def m(self) -> int:
        """Edge count of the published generation."""
        return self._buf.m

    def identity_carry(self) -> jax.Array:
        """Position carry of a no-splice step (flush-triggered rebuilds)."""
        if self._identity is None or self._identity.shape[0] != self.e_cap:
            self._identity = jnp.arange(self.e_cap, dtype=jnp.int32)
        return self._identity

    def apply_delta(self, dyn: "DynamicGraph", delta: "DeltaResult",
                    del_pos: np.ndarray, old_deg_touched: np.ndarray,
                    m_old: int) -> None:
        """Mirror one already-applied host delta with delta-sized uploads."""
        with trace.span("graph.device_delta", touched=int(delta.touched.size),
                        inserted=int(delta.inserted.shape[0]),
                        deleted=int(delta.deleted.shape[0])) as dsp:
            self._apply_delta(dyn, delta, del_pos, old_deg_touched, m_old)
            dsp.fence((self.adj, self.deg, self.edges))

    def _apply_delta(self, dyn: "DynamicGraph", delta: "DeltaResult",
                     del_pos: np.ndarray, old_deg_touched: np.ndarray,
                     m_old: int) -> None:
        """The untraced body of :meth:`apply_delta` — shadow build + swap."""
        # donation consumes the input buffer, which is exactly the published
        # generation an in-flight reader may still be using: only donate
        # when the graph's donation policy proves nothing does
        donate = dyn.donate_ok()
        if donate:
            self.meter.count_donation()
        _scatter_rows, _scatter_vals, _splice_edges = _update_fns(donate)
        n = self.n
        deg, adj, edges, e_cap = (self._buf.deg, self._buf.adj,
                                  self._buf.edges, self._buf.e_cap)
        cap = dyn.capacity
        if adj.shape[1] < cap:               # headroom growth, device-side
            adj = jnp.pad(adj, ((0, 0), (0, cap - adj.shape[1])),
                          constant_values=n)
        touched = delta.touched
        if touched.size:
            # per-row width covers the row before AND after the delta so
            # untouched columns are sentinel on both sides of the scatter;
            # rows are partitioned by pow2 width bucket so one hub does not
            # inflate every row's upload to its width (≤ log(cap) scatters,
            # each a reused compiled variant)
            with trace.span("graph.scatter_rows", rows=int(touched.size)):
                wv = np.maximum(np.maximum(old_deg_touched,
                                           dyn.deg[touched]), 1)
                wb = np.minimum(2 ** np.ceil(np.log2(wv)).astype(np.int64)
                                .clip(min=0), cap)
                for width in np.unique(wb):
                    grp = touched[wb == width]
                    w_b = int(width)
                    t_b = pow2_bucket(grp.size)
                    verts = np.full(t_b, n, dtype=np.int32)
                    verts[:grp.size] = grp
                    rows = np.full((t_b, w_b), n, dtype=np.int32)
                    rows[:grp.size] = dyn.adj[grp, :w_b]
                    adj = _scatter_rows(adj, self.meter.put(verts),
                                        self.meter.put(rows))
                # degrees are width-independent: one scatter over all touched
                t_b = pow2_bucket(touched.size)
                verts = np.full(t_b, n, dtype=np.int32)
                verts[:touched.size] = touched
                degs = np.zeros(t_b, dtype=np.int32)
                degs[:touched.size] = dyn.deg[touched]
                deg = _scatter_vals(deg, self.meter.put(verts),
                                    self.meter.put(degs))

        n_ins = int(delta.inserted.shape[0])
        with trace.span("graph.splice_edges", inserts=n_ins,
                        deletes=int(del_pos.size)):
            if e_cap < m_old + n_ins:        # edge buffer growth, device-side
                new_cap = pow2_bucket(m_old + n_ins)
                edges = jnp.pad(edges, ((0, new_cap - e_cap), (0, 0)),
                                constant_values=n)
                e_cap = new_cap
            i_b, d_b = pow2_bucket(n_ins), pow2_bucket(del_pos.size)
            dpos = np.full(d_b, e_cap, dtype=np.int32)       # sentinel: drop
            dpos[:del_pos.size] = del_pos
            ipos = np.full(i_b, e_cap, dtype=np.int32)
            ipos[:n_ins] = m_old + np.arange(n_ins)
            iuv = np.full((i_b, 2), n, dtype=np.int32)
            iuv[:n_ins] = delta.inserted
            edges, self.last_carry = _splice_edges(
                edges, self.meter.put(dpos), self.meter.put(ipos),
                self.meter.put(iuv), m_old, n)
        # publication: one atomic swap — no reader ever observes a mix of
        # generations
        self._buf = _DeviceBuffers(deg, adj, edges, e_cap, dyn.m)


class HostGraphSnapshot:
    """Frozen host-side view of a :class:`DynamicGraph` at one version.

    ``deg``/``edge_keys`` are captured by reference — deltas rebind those
    arrays on the graph, so the captured ones never change again. The padded
    adjacency *is* mutated in place (that is the point of the headroom), so
    the snapshot keeps a copy-on-write row overlay: just before a delta
    overwrites a row the graph pushes the pre-delta bytes into every live
    snapshot's overlay (:meth:`DynamicGraph._shield_snapshots`), a cost
    sized by the delta and the number of live snapshots, never by n. On
    capacity growth the adjacency is rebound instead, which freezes the old
    array for free — the identity check in :meth:`_save_rows_locked`
    notices.

    Snapshots are read concurrently with delta application (that is the
    whole point), so shield+overwrite on the delta thread and the
    overlay-miss → live-row read in :meth:`neighbors` synchronize on the
    graph's shared ``_row_lock``; see :meth:`neighbors` for the protocol.
    """

    # machine-checked lock discipline (tools/pgcheck PG001): the overlay is
    # shared between the delta thread (shield) and snapshot readers (miss
    # path) — both sides hold the graph's row lock. The one intentional
    # unlocked probe in `neighbors` carries its own suppression.
    _GUARDED_BY = {
        "_overlay": "_lock",
    }

    __slots__ = ("n", "m", "version", "deg", "edge_keys", "_adj", "_overlay",
                 "_lock", "__weakref__")

    def __init__(self, dyn: "DynamicGraph"):
        self.n = dyn.n
        self.m = dyn.m
        self.version = dyn.version
        self.deg = dyn.deg
        self.edge_keys = dyn.edge_keys
        self._adj = dyn.adj
        self._overlay = {}
        self._lock = dyn._row_lock

    def _save_rows_locked(self, adj: np.ndarray,
                          touched: np.ndarray) -> None:
        # first save wins: the overlay must hold the row as of snapshot
        # creation, and a vertex touched twice was already saved pre-first-
        # mutation (rows untouched since creation are read live — identical)
        # caller (_shield_snapshots) holds the shared row lock
        if self._adj is not adj:
            return                        # adjacency was rebound: frozen
        overlay = self._overlay
        for v in touched:
            iv = int(v)
            if iv not in overlay:
                overlay[iv] = np.array(adj[iv], copy=True)

    def neighbors(self, v: int) -> np.ndarray:
        """Sorted neighbor ids of ``v`` at the snapshot's version.

        Safe against a delta landing concurrently: the delta thread holds
        the graph's row lock across "save pre-delta rows into overlays,
        then overwrite" (:meth:`DynamicGraph._apply_delta`), so under the
        same lock either the overlay already has the pre-delta row or the
        live row still *is* the pre-delta row — and the live-row path
        returns a copy taken inside the lock, so the result cannot change
        between return and consumption. Overlay rows are private frozen
        copies; slicing them needs no copy. The unlocked first probe is
        sound: a hit is immutable, and a miss is re-checked under the lock.
        """
        iv = int(v)
        # double-checked locking: a hit is an immutable private row, and a
        # miss is re-probed under the lock just below
        row = self._overlay.get(iv)  # pgcheck: disable=PG001
        if row is None:
            with self._lock:
                row = self._overlay.get(iv)
                if row is None:
                    return self._adj[iv, :self.deg[iv]].copy()
        return row[:self.deg[iv]]


class DynamicGraph:
    """Mutable undirected graph on a fixed vertex set with batched deltas."""

    # machine-checked lock discipline (tools/pgcheck PG001): the delta
    # thread's shield-then-overwrite of `adj`/`deg` must be one critical
    # section with snapshot row reads (`write:` — host reads are the common
    # case and synchronize through snapshot capture, not the lock).
    _GUARDED_BY = {
        "adj": "write:_row_lock",
        "deg": "write:_row_lock",
    }

    def __init__(self, n: int, edge_keys: np.ndarray, deg: np.ndarray,
                 adj: np.ndarray, headroom: float = 1.5, version: int = 0):
        self.n = int(n)
        self.edge_keys = edge_keys        # sorted int64[m], key = lo*n + hi
        self.deg = deg                    # int32[n]
        self.adj = adj                    # int32[n, cap]; rows sorted, pad = n
        self.headroom = float(headroom)
        self.version = int(version)
        self.traffic = TrafficMeter()
        self._device: Optional[DeviceGraphState] = None
        self._snapshots: "weakref.WeakSet[HostGraphSnapshot]" = \
            weakref.WeakSet()
        # shared with every HostGraphSnapshot: serializes the delta thread's
        # shield-then-overwrite against concurrent snapshot row reads
        self._row_lock = threading.Lock()
        # a StreamSession installs its lease-aware donation policy here;
        # a bare DynamicGraph falls back to "any live snapshot vetoes"
        self._donation_guard = None

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def from_edges(cls, n: int, edges, headroom: float = 1.5,
                   min_width: int = 4) -> "DynamicGraph":
        """Build from a raw edge array (duplicates/self-loops dropped)."""
        keys = canonical_edge_keys(n, edges)
        deg, adj = _build_adjacency(n, keys, headroom, min_width)
        return cls(n, keys, deg, adj, headroom)

    @classmethod
    def from_graph(cls, graph: Graph, headroom: float = 1.5) -> "DynamicGraph":
        """Build from a frozen :class:`~repro.core.graph.Graph`."""
        return cls.from_edges(graph.n, np.asarray(graph.edges),
                              headroom=headroom)

    # ------------------------------------------------------------------
    # views
    # ------------------------------------------------------------------

    @property
    def m(self) -> int:
        """Current number of (canonical, undirected) edges."""
        return int(self.edge_keys.shape[0])

    @property
    def capacity(self) -> int:
        """Adjacency row width (degree headroom included)."""
        return int(self.adj.shape[1])

    def neighbors(self, v: int) -> np.ndarray:
        """Sorted neighbor ids of ``v`` (host view, no padding)."""
        return self.adj[v, :self.deg[v]]

    def edge_array(self) -> np.ndarray:
        """int64[m, 2] canonical (u < v) edges in key order."""
        return _decode_keys(self.n, self.edge_keys)

    @property
    def pinned(self) -> bool:
        """True while any live :class:`HostGraphSnapshot` pins published
        state (device buffer donation must then be off — see
        ``_update_fns``)."""
        return len(self._snapshots) > 0

    def snapshots(self) -> Tuple[HostGraphSnapshot, ...]:
        """The currently live (weakly tracked) host snapshots."""
        return tuple(self._snapshots)

    def donate_ok(self) -> bool:
        """May the next device update donate the published buffers?

        A :class:`~repro.stream.session.StreamSession` installs a guard
        that tracks serving read-leases and stale views, so donation
        re-engages whenever only the session's own published view is
        alive and nobody is reading it. Without a guard, any live host
        snapshot vetoes donation (the conservative standalone default).
        """
        if self._donation_guard is not None:
            return bool(self._donation_guard())
        return not self.pinned

    def host_snapshot(self) -> HostGraphSnapshot:
        """Capture a frozen host view of the current version.

        The snapshot stays valid (and delta-sized cheap) across any number
        of later deltas; it is tracked by weak reference, so dropping it
        releases its overlay and its donation pin automatically.
        """
        snap = HostGraphSnapshot(self)
        self._snapshots.add(snap)
        return snap

    def _shield_snapshots(self, touched: np.ndarray) -> None:
        """Copy the about-to-be-overwritten adjacency rows into every live
        snapshot's overlay (called by ``_apply_delta`` pre-mutation)."""
        if self._snapshots:
            for snap in tuple(self._snapshots):
                snap._save_rows_locked(self.adj, touched)

    @property
    def device(self) -> DeviceGraphState:
        """The device-resident mirror, created (one full upload) on first use
        and kept current by every subsequent ``apply_delta``."""
        if self._device is None:
            self._device = DeviceGraphState(self, self.traffic)
        return self._device

    def view(self) -> Graph:
        """Lightweight ``Graph`` over the live device buffers — the streaming
        hot path's graph, built with zero host → device traffic.

        Value-identical to ``snapshot()`` everywhere an algorithm reads it
        (same deg/edges/CSR contents; the padded adjacency only carries extra
        sentinel columns, which every consumer ignores); the next
        ``apply_delta`` supersedes it, so sessions must repoint at a fresh
        view per delta (``StreamSession`` does).
        """
        buf = self.device._buf             # one read: a concurrent publish
        return graph_view(self.n, buf.m, buf.deg, buf.adj,
                          buf.edges[:buf.m])   # must not mix generations

    def snapshot(self) -> Graph:
        """Explicit full host materialization: a device ``Graph`` that is
        bit-identical (arrays and static fields) to
        ``from_edge_array(n, self.edge_array())``. The streaming hot path
        never calls this — only ``save()``/``--verify``-style consumers do;
        serving reads ``view()`` instead.

        Every numpy buffer handed to jax is a fresh copy: ``jnp.asarray`` of
        a host array can be zero-copy on CPU, and ``self.adj``/``self.deg``
        are mutated in place by later deltas — an aliased device view would
        change under any still-in-flight async computation.
        """
        n = self.n
        d_max = max(int(self.deg.max()) if n else 0, 1)
        mask = np.arange(self.capacity)[None, :] < self.deg[:, None]
        indices = self.adj[mask].astype(np.int32)      # row-major == CSR order
        indptr = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(self.deg, out=indptr[1:])
        adj = self.adj[:, :d_max] if self.capacity >= d_max else np.pad(
            self.adj, ((0, 0), (0, d_max - self.capacity)), constant_values=n)
        return Graph(
            indptr=jnp.asarray(indptr),
            indices=jnp.asarray(indices),
            adj=jnp.asarray(np.array(adj, copy=True)),
            deg=jnp.asarray(self.deg.copy()),
            edges=jnp.asarray(self.edge_array().astype(np.int32)),
            n_vertices=n, n_edges=self.m, d_max=d_max)

    # ------------------------------------------------------------------
    # mutation
    # ------------------------------------------------------------------

    def apply_delta(self, inserts=None, deletes=None) -> DeltaResult:
        """Apply one batch of edge insertions and deletions.

        Both arguments are (possibly duplicated / both-direction / already
        present or absent) edge arrays; the applied delta is canonicalized:
        deletes that miss and inserts that already exist are dropped.
        Deletes are applied before inserts, so an edge listed in both ends
        up present (and both endpoints count as dirty).
        """
        with trace.span("graph.apply_delta") as sp:
            delta = self._apply_delta(inserts, deletes)
            sp.set(inserted=int(delta.inserted.shape[0]),
                   deleted=int(delta.deleted.shape[0]),
                   touched=int(delta.touched.size), version=delta.version)
            return delta

    def _apply_delta(self, inserts, deletes) -> DeltaResult:
        """The untraced body of :meth:`apply_delta`."""
        n = self.n
        cur = self.edge_keys
        del_req = canonical_edge_keys(n, deletes)
        del_applied = del_req[np.isin(del_req, cur, assume_unique=True)]
        kept = (cur[~np.isin(cur, del_applied, assume_unique=True)]
                if del_applied.size else cur)
        ins_req = canonical_edge_keys(n, inserts)
        ins_applied = (ins_req[~np.isin(ins_req, kept, assume_unique=True)]
                       if ins_req.size else ins_req)

        ins_uv = _decode_keys(n, ins_applied)
        del_uv = _decode_keys(n, del_applied)
        self.version += 1
        if ins_applied.size == 0 and del_applied.size == 0:
            return DeltaResult(ins_uv, del_uv, np.zeros(0, np.int64),
                               np.zeros(0, np.int64), self.version)

        # positions of the deleted edges in the *old* canonical order — the
        # device edge-splice scatters these before the host order changes
        del_pos = np.searchsorted(cur, del_applied).astype(np.int64)
        m_old = int(cur.shape[0])
        self.edge_keys = np.union1d(kept, ins_applied)
        touched = np.unique(np.concatenate([ins_uv.ravel(), del_uv.ravel()]))
        dirty = np.unique(del_uv.ravel())
        old_deg_touched = self.deg[touched].copy()

        new_deg = self.deg.astype(np.int64)
        if ins_uv.size:
            new_deg += np.bincount(ins_uv.ravel(), minlength=n)
        if del_uv.size:
            new_deg -= np.bincount(del_uv.ravel(), minlength=n)
        need = int(new_deg.max())
        grown = None
        if need > self.capacity:
            # grow with headroom so a run of inserts amortizes reallocation;
            # built here, but rebound onto self.adj only inside the row
            # lock below — the rebind is a write to published state
            cap = max(need, int(math.ceil(need * self.headroom)))
            grown = np.full((n, cap), n, dtype=np.int32)
            grown[:, :self.capacity] = self.adj
        new_cap = grown.shape[1] if grown is not None else self.capacity

        # vectorized touched-row rewrite (np.unique/offset-scatter, the
        # DeltaResult.insert_rows technique — no per-vertex Python loop):
        # collect the touched rows' surviving half-edges plus the inserted
        # ones, lexsort by (src, dst), and scatter each group back into its
        # row at within-group rank. Bit-identical to the old per-row
        # delete/concat/sort because both produce ascending neighbor lists
        # padded with the sentinel n.
        old_counts = old_deg_touched.astype(np.int64)
        mask = np.arange(self.capacity)[None, :] < old_counts[:, None]
        src = np.repeat(touched, old_counts)
        dst = self.adj[touched][mask].astype(np.int64)
        if del_uv.size:
            del_keys = np.concatenate([del_uv[:, 0] * n + del_uv[:, 1],
                                       del_uv[:, 1] * n + del_uv[:, 0]])
            keep = ~np.isin(src * n + dst, del_keys)
            src, dst = src[keep], dst[keep]
        if ins_uv.size:
            src = np.concatenate([src, ins_uv[:, 0], ins_uv[:, 1]])
            dst = np.concatenate([dst, ins_uv[:, 1], ins_uv[:, 0]])
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
        rows_new = np.full((touched.size, new_cap), n, dtype=np.int32)
        if src.size:
            verts, start = np.unique(src, return_index=True)
            counts = np.diff(np.append(start, src.size))
            row = np.repeat(np.searchsorted(touched, verts), counts)
            col = np.arange(src.size) - np.repeat(start, counts)
            rows_new[row, col] = dst
        # rebind + shield + overwrite are one critical section: a snapshot
        # reader that misses the overlay and falls through to the live row
        # must never observe the row post-overwrite
        # (HostGraphSnapshot.neighbors takes the same lock). The rebind
        # happens first so shielding sees the new array and skips copies —
        # the old array is frozen by the rebind, exactly what snapshots
        # captured (`_save_rows_locked`'s identity check).
        with self._row_lock:
            if grown is not None:
                self.adj = grown
            self._shield_snapshots(touched)
            self.adj[touched] = rows_new
            self.deg = new_deg.astype(np.int32)
        delta = DeltaResult(ins_uv, del_uv, touched, dirty, self.version)
        if self._device is not None:
            self._device.apply_delta(self, delta, del_pos, old_deg_touched,
                                     m_old)
        return delta

    def carry_index(self, old_keys: np.ndarray,
                    invalid_vertices: np.ndarray) -> Optional[np.ndarray]:
        """Map current edges to their row in a previous edge order.

        Returns int64[m] where entry j is the position of edge j in
        ``old_keys`` (a previous sorted ``edge_keys``) when neither endpoint
        is in ``invalid_vertices``, else -1 — exactly the
        ``MiningSession.refresh`` carry contract.
        """
        new_keys = self.edge_keys
        if self.n == 0 or new_keys.size == 0:
            return np.zeros(0, dtype=np.int64)
        if old_keys.size == 0:
            return np.full(new_keys.shape[0], -1, dtype=np.int64)
        pos = np.searchsorted(old_keys, new_keys)
        pos_c = np.minimum(pos, old_keys.size - 1)
        found = old_keys[pos_c] == new_keys
        bad = np.zeros(self.n, dtype=bool)
        bad[np.asarray(invalid_vertices, dtype=np.int64)] = True
        lo, hi = new_keys // self.n, new_keys % self.n
        return np.where(found & ~bad[lo] & ~bad[hi], pos_c, -1).astype(np.int64)


# ----------------------------------------------------------------------------
# host helpers
# ----------------------------------------------------------------------------



def _decode_keys(n: int, keys: np.ndarray) -> np.ndarray:
    if keys.size == 0:
        return np.zeros((0, 2), dtype=np.int64)
    return np.stack([keys // n, keys % n], axis=1)


def _build_adjacency(n: int, keys: np.ndarray, headroom: float,
                     min_width: int) -> Tuple[np.ndarray, np.ndarray]:
    uv = _decode_keys(n, keys)
    src = np.concatenate([uv[:, 0], uv[:, 1]])
    dst = np.concatenate([uv[:, 1], uv[:, 0]])
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    deg = np.bincount(src, minlength=n).astype(np.int32)
    d_max = int(deg.max()) if n else 0
    cap = max(min_width, int(math.ceil(max(d_max, 1) * headroom)))
    adj = np.full((n, cap), n, dtype=np.int32)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])
    col = np.arange(src.size) - indptr[src]
    adj[src.astype(np.int64), col] = dst.astype(np.int32)
    return deg, adj
