"""Latency and rate arithmetic shared by the harness and the readers."""
from __future__ import annotations

import math
import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the sample at or below it.  A missing event is ``inf`` and counts as
    missing every limit, so a tail over failures reads ``inf``."""
    x = np.sort(np.asarray(values, np.float64))
    if not len(x):
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * len(x)))
    return float(x[rank - 1])


def rate(count: int, seconds: float) -> float:
    return count / seconds


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles``, the exclusive method)."""
    q1, q2, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / q2

