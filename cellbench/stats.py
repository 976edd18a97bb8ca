"""Percentile, rate and interval arithmetic (no dependency on the program)."""

from __future__ import annotations


def percentile(values, q: float) -> float | None:
    """q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default rule); None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def rate(amount: float, seconds: float) -> float | None:
    return amount / seconds if seconds > 0 else None


def merged(intervals) -> list[tuple[float, float]]:
    """The union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
    return sum(e - s for s, e in merged(intervals))


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]
