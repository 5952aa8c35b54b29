"""Percentiles and spreads over whole samples.

Every percentile here is taken over all the values it is given (every
request or every gap of a window), never as a median of per-chunk
percentiles.  ``spread`` is the benchmark's noise measure: the distance
between the first and third quartile, as ``statistics.quantiles(values,
n=4)`` gives them, over the median.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, as numpy's default method computes it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    h = (len(xs) - 1) * q / 100.0
    lo = math.floor(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def spread(values) -> float:
    """(Q3 - Q1) / median over the runs of one set."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def rate(count: float, seconds: float) -> float:
    """Work per second over a whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds
