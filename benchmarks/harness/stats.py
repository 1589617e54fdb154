"""Order statistics the benchmark reports.  One definition each, so every PR
computes a tail the same way."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (``p`` in (0, 100]): the smallest value with at
    least ``p`` % of the sample at or below it.  No interpolation: a reported
    tail is a time some request really saw."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def median(values: Sequence[float]) -> float:
    return statistics.median(values)


def mean(values: Sequence[float]) -> float:
    return statistics.fmean(values)


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median — the spread the
    bounds are set from (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


STATS = {
    "median": median, "mean": mean, "max": max, "min": min,
    "sum": sum, "count": len,
    "p90": lambda v: percentile(v, 90), "p95": lambda v: percentile(v, 95),
    "p99": lambda v: percentile(v, 99),
}
