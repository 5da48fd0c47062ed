"""Operations and bytes of the kernels the benchmark reads a roofline for,
computed from shapes alone, so the same work is counted whatever
implements it.

The shared card pass (``MiningSession.edge_cardinalities``) reads, for each
of E edges, the two endpoints' sketch rows of W uint32 words, ANDs them and
writes one estimate. Its least HBM traffic reads every sketch row once
(n · W · 4 bytes), the edges' endpoint ids (E · 2 · 4) and writes one
float32 per edge (E · 4): the unique-row bound, which a kernel that keeps
hub rows on the chip can reach. The gathered traffic, both rows read per
edge (E · 2 · W · 4), is the plain gather's and is kept for context only.
The pass does integer AND + popcount, so HBM bandwidth bounds it.
"""
from __future__ import annotations

WORD_BYTES = 4


def card_pass_min_bytes(n: int, words: int, edges: int) -> int:
    """Unique-row HBM bytes of one card pass."""
    return n * words * WORD_BYTES + edges * 2 * 4 + edges * 4


def card_pass_gathered_bytes(words: int, edges: int) -> int:
    """Bytes of both sketch rows gathered per edge (context, not a bound)."""
    return edges * 2 * words * WORD_BYTES


def roofline_pct(min_bytes: int, hbm_bytes_per_s: float,
                 busy_s: float) -> float | None:
    """Least HBM time over device-busy time, in percent (None when the
    trace shows no device time)."""
    if not busy_s:
        return None
    return 100.0 * (min_bytes / hbm_bytes_per_s) / busy_s
