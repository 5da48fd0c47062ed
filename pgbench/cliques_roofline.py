"""Bytes of the 4-clique cell's two passes, computed from shapes alone, so
the same work is counted whatever implements it (``pgbench/roofline.py``
holds the peak arithmetic).

Triangle listing (``MiningSession.triangles``) over n vertices, m edges and
T triangles: its least HBM traffic reads the symmetric CSR it starts
from ((n + 1) · 4 + 2m · 4), the degrees (n · 4), the oriented CSR's row
pointers ((n + 1) · 4) and neighbour ids (m · 4), and writes the list
(T · 12). The wedge probes in between are the
algorithm's, not a bound.

The 3-way AND (``four_clique_count`` on a Bloom sketch of W words) reads
every sketch row once (n · W · 4), the list (T · 12) and writes one float32
estimate per triangle (T · 4): the unique-row bound. The gathered traffic,
three rows per triangle (T · 3 · W · 4), is the plain gather's and is kept
for context only. Both passes are integer work, so HBM bandwidth bounds
them.
"""
from __future__ import annotations

WORD_BYTES = 4


def triangle_list_min_bytes(n: int, m: int, triangles: int) -> int:
    """Least HBM bytes of one triangle listing."""
    return ((n + 1) * 4 + 2 * m * 4 + n * 4 + (n + 1) * 4 + m * 4
            + triangles * 12)


def triple_and_min_bytes(n: int, words: int, triangles: int) -> int:
    """Unique-row HBM bytes of one 3-way AND pass over the list."""
    return n * words * WORD_BYTES + triangles * 12 + triangles * 4


def triple_and_gathered_bytes(words: int, triangles: int) -> int:
    """Bytes of the three sketch rows gathered per triangle (context)."""
    return triangles * 3 * words * WORD_BYTES
