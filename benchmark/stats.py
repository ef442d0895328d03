"""Arithmetic of the end-to-end metrics, kept where no later PR changes it.

Every rate is all the work over all the window; a percentile is exact, by
nearest rank over the full sample (never a decaying recorder's estimate).
"""
import math
import statistics

import numpy as np


def percentile(sample, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of the
    sample at or below it. `sample` need not be sorted."""
    a = np.asarray(sample)
    if a.size == 0:
        raise ValueError("percentile of an empty sample")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q={q} outside (0, 1]")
    k = max(1, math.ceil(q * a.size)) - 1
    return float(np.partition(a, k)[k])


def rate(count: float, window_s: float) -> float:
    """Work over the window (drain included: see each driver)."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return count / window_s


def gbps(nbytes: float, window_s: float) -> float:
    """GB/s with 1 GB = 1e9 B."""
    return rate(nbytes, window_s) / 1e9


def spread(values) -> float:
    """Distance between the first and third quartile over the median, as
    `statistics.quantiles(values, n=4)` gives them (the driver's rule)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
