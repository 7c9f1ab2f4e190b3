"""Percentiles of a sample and the spread of repeated runs."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """The ``p``-th percentile (0..100) by linear interpolation between the
    closest ranks: rank ``(n - 1) * p / 100`` of the sorted sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    h = (len(xs) - 1) * p / 100.0
    lo = math.floor(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
