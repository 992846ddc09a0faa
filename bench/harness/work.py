"""The bytes a kernel's algorithm has to move, from the cell's shapes.

These count what the algorithm needs (unpadded: Q queries, k1 or
k2 * (2h + 1) candidates, d dimensions), not the tiles a kernel pads to,
so a kernel that replaces another is held to the same work.  Sketches hold
one bit per dimension in uint32 words; codes hold ``log2(levels)`` bits
per dimension, eight 4-bit codes to a uint32 word.
"""

from __future__ import annotations


def _words(bits: int) -> int:
    return -(-bits // 32)


def hamming_rows_bytes(queries: int, k1: int, dim: int) -> int:
    """One stage-1 Hamming call: each query's sketch against its own k1
    candidate sketches -> (Q, k1) int32 distances."""
    w = _words(dim)
    return 4 * (queries * w + queries * k1 * w + queries * k1)


def qdist_windows_bytes(queries: int, k2: int, h: int, dim: int,
                        levels: int) -> int:
    """One stage-2 call: float32 queries against their own k2 * (2h + 1)
    packed candidate codes and the (dim, levels) centroid table -> (Q, C)
    float32 distances."""
    c = k2 * (2 * h + 1)
    w = _words(dim * (levels.bit_length() - 1))
    return 4 * (queries * dim + queries * c * w + dim * levels + queries * c)
