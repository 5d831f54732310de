"""The work each kernel's algorithm needs, from its shapes alone.

Counts are of what the algorithm must do, not of how a kernel does it, so
a share of the roofline reads the same however a later change implements
the kernel.
"""

from __future__ import annotations


def lbc(queries: int, rows: int, segments: int) -> tuple:
    """(operations, bytes) of one lower-bound pass of Q queries over N rows.

    Bytes: the N x w uint8 SAX words, read once. Operations: per query,
    row and segment a bound lookup, a difference, a square and an add.
    The (Q, N) bounds the kernel writes today are not counted: a streamed
    bound-and-select would not write them.
    """
    return 4.0 * queries * rows * segments, 1.0 * rows * segments


def paa_isax(rows: int, length: int, segments: int,
             cardinality: int) -> tuple:
    """(operations, bytes) of converting N float32 series to iSAX words.

    Bytes: the N x n float32 series read and the N x w uint8 words
    written. Operations: one add per point for the segment sums, one
    divide per segment, and a binary search over the breakpoints
    (log2(cardinality) compares) per segment.
    """
    search = (cardinality - 1).bit_length()
    ops = 1.0 * rows * length + rows * segments * (1 + search)
    return ops, 4.0 * rows * length + 1.0 * rows * segments
