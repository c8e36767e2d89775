"""The arithmetic from records to numbers."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest rank: the smallest value with at least p% of the sample
    at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, merged (start, end) intervals; empty ones dropped."""
    merged: list[list[int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def covered(merged) -> int:
    return sum(end - start for start, end in merged)


def gaps(merged, window: tuple[int, int]) -> list[tuple[int, int]]:
    """What `window` holds outside the merged intervals, edges included."""
    lo, hi = window
    out, cursor = [], lo
    for start, end in merged:
        if end <= lo or start >= hi:
            continue
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < hi:
        out.append((cursor, hi))
    return out
