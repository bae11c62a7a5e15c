"""Statistics of a run and of a series of runs.

``percentile`` is the nearest-rank percentile (exact on the sorted sample,
no interpolation). ``spread`` is the distance between the first and the
third quartile, as ``statistics.quantiles(values, n=4)`` gives them, as a
share of the median: the measure the benchmark's bounds are set from.
"""
from __future__ import annotations

import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    rank = max(1, -(-len(s) * q // 100))        # ceil(n*q/100), at least 1
    return float(s[int(rank) - 1])


def beyond(n: int, q: float) -> int:
    """Samples of ``n`` that lie beyond the nearest-rank ``q`` percentile."""
    return n - max(1, -(-n * q // 100))


def rate(count: float, seconds: float) -> float:
    """A count over the whole of a window."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return count / seconds


def spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median of a series of at least two readings."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
