"""Order statistics for the benchmark's own numbers."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


class EmptySample(ValueError):
    """A percentile of no values: the run has nothing to report."""


def percentile(values: Sequence[float], p: float) -> float:
    """The ``p``-th percentile (0-100) by linear interpolation between
    closest ranks. An empty sample raises: it is a failed run, not a
    perfect latency."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise EmptySample(f"percentile {p} of an empty sample")
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile must lie in [0, 100], got {p}")
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median, with the
    quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
