"""Small CPU-sized copies of the benchmark's configurations, for the tests
in this directory (run by hand: ``python -m pytest pgbench/tests``)."""
from __future__ import annotations

import math
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from pgbench import harness  # noqa: E402
from pgbench.gen import kronecker as K  # noqa: E402


def bloom_words(n: int, m: int, budget: float) -> int:
    """ProbGraph's Bloom width for a storage budget (words per vertex)."""
    words = max(int(math.ceil(max(1.0, budget * (2 * m + n + 1) * 32 / n)
                              / 32)), 2)
    return words + words % 2


def small_cell(name: str, scale: int = 9) -> harness.Cell:
    """The cell with its configuration cut to ``scale`` and its pads, widths
    and limits recomputed for that graph."""
    cell = harness.Cell(name)
    cfg = dict(cell.config, scale=scale)
    n = 1 << scale
    keys = K.structure(cfg)
    deg = np.bincount(K.decode(n, keys).ravel(), minlength=n)
    cfg["m"] = int(keys.size)
    cfg["adj_width"] = int(deg.max())
    cfg["words"] = bloom_words(n, cfg["m"], cfg["storage_budget"])
    cell.config = cfg
    return cell


def run_small(cell: harness.Cell, seed: int = 7, seconds: float = 0.0,
              trace: bool = False) -> dict:
    """One run of a small cell on the CPU, past the look for a chip."""
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            require_tpu=False)
