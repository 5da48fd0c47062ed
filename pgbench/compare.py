"""Comparisons shared by the output checks in ``pgbench/checks/``."""
from __future__ import annotations

import numpy as np


def rel_gap(got, want, floor: float = 1.0) -> float:
    """max |got - want| / max(|want|, floor); inf on a shape mismatch."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    gap = np.abs(got - want) / np.maximum(np.abs(want), floor)
    return float(np.nan_to_num(gap, nan=np.inf).max())


def rows_differing(got, want) -> int:
    """Rows of ``got`` not equal to ``want`` (all of them on a shape
    mismatch)."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return int(max(len(got), len(want)))
    return int(np.any(got.reshape(len(got), -1) != want.reshape(len(want), -1),
                      axis=1).sum())
