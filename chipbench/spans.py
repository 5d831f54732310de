"""Interval sums over a :class:`chipbench.trace.Trace`, for the readers of
the program's own spans and scopes.

Times are ns on the trace's one clock. Every interval is cut to the traced
window, and overlapping intervals count once: a while op overlaps the ops
of its body, and the build's convert stage runs on several threads.
"""

from __future__ import annotations


def union(intervals) -> list:
    """Sorted, disjoint ``[start, end]`` covering ``intervals``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def length(intervals) -> float:
    """ns covered by ``intervals``."""
    return sum(e - s for s, e in union(intervals))


def overlap(a, b) -> float:
    """ns covered by both ``a`` and ``b``."""
    a, b = union(a), union(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clipped(trace, events) -> list:
    """``(start, end)`` of each event, cut to the window."""
    return [(max(e.start, trace.start), min(e.end, trace.end))
            for e in events]


def named(trace, name: str) -> list:
    """The host spans called ``name`` inside the window."""
    return [h for h in trace.host if h.name == name]
