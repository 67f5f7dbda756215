"""The benchmark's own arithmetic over raw samples."""
from __future__ import annotations

import math
import statistics


def percentile(xs, p: float) -> float | None:
    """The ``p``-th percentile of all samples ``xs`` by linear
    interpolation between closest ranks (numpy's default, and
    ``statistics.quantiles(method="inclusive")``); None when empty."""
    xs = sorted(xs)
    if not xs:
        return None
    rank = p / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, the quartiles as ``statistics.quantiles(values, n=4)`` gives
    them (the rule the benchmark's bounds are set by)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def union_seconds(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, end = 0.0, -math.inf
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total
