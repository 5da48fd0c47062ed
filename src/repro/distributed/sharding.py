"""Edge-axis sharding of the mining fold: logical axes -> mesh axes.

The engine names the axis it splits, ``"edge"``, and a rule maps it onto
the physical mesh axes ("pod", "data", "model"). ``use_rules(mesh)``
installs the mesh for the calling thread; ``engine._edge_shards`` reads it
back through ``active_mesh()`` and asks ``spec_for(("edge",))`` which of
its axes the edge list splits over. Without an active mesh the sharded
plan falls back to the one-device fold.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

from jax.sharding import Mesh, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]

# Edge lists shard over every mesh axis: fixed-size sketches make per-edge
# work uniform, so any split balances.
RULES: Dict[str, Axis] = {
    "edge": ("pod", "data", "model"),
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None


_CTX = _Ctx()


def active_mesh() -> Optional[Mesh]:
    """The mesh installed by ``use_rules`` (None outside any context)."""
    return _CTX.mesh


@contextlib.contextmanager
def use_rules(mesh: Optional[Mesh]):
    """Install ``mesh`` as this thread's active mesh for the block."""
    prev = _CTX.mesh
    _CTX.mesh = mesh
    try:
        yield
    finally:
        _CTX.mesh = prev


def _filter_axes(mesh: Mesh, phys: Axis, dim_size: int, used: set) -> Axis:
    """Drop mesh axes that don't divide the dim or are already used."""
    if phys is None:
        return None
    axes = (phys,) if isinstance(phys, str) else tuple(phys)
    kept = []
    size = 1
    for a in axes:
        if a not in mesh.shape or a in used:
            continue
        asize = mesh.shape[a]
        if dim_size % (size * asize) != 0:
            continue
        kept.append(a)
        size *= asize
    if not kept:
        return None
    for a in kept:
        used.add(a)
    return tuple(kept) if len(kept) > 1 else kept[0]


def spec_for(logical_axes: Sequence[Optional[str]],
             dim_sizes: Optional[Sequence[int]] = None,
             mesh: Optional[Mesh] = None,
             rules: Optional[Dict[str, Axis]] = None) -> P:
    """Logical axes -> PartitionSpec under ``rules`` (default ``RULES``).

    Mesh axes missing from the mesh are dropped, as are axes that an
    earlier dimension already took; with ``dim_sizes`` an axis is kept
    only while the product of kept axis sizes divides the dimension.
    """
    mesh = mesh or _CTX.mesh
    rules = rules or RULES
    used: set = set()
    parts = []
    for i, name in enumerate(logical_axes):
        phys = rules.get(name) if name else None
        if mesh is not None and phys is not None:
            size = dim_sizes[i] if dim_sizes is not None else None
            if size is not None:
                phys = _filter_axes(mesh, phys, size, used)
            else:
                axes = (phys,) if isinstance(phys, str) else tuple(phys)
                axes = tuple(a for a in axes if a in mesh.shape and a not in used)
                for a in axes:
                    used.add(a)
                phys = axes if len(axes) > 1 else (axes[0] if axes else None)
        parts.append(phys)
    return P(*parts)
