"""Percentiles and spreads, the same in every run and every PR."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p`` % of
    the values at or below it. Every value counts; the caller puts a request
    that never finished in at its limit."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    return xs[percentile_rank(len(xs), p) - 1]


def percentile_rank(n: int, p: float) -> int:
    """The 1-based rank :func:`percentile` takes among ``n`` values."""
    return max(1, math.ceil(p / 100.0 * n))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (Python's ``statistics.quantiles`` quartiles)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
