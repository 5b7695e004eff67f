"""Arithmetic from per-request times to metrics.

Kept with the benchmark so that every PR computes the same number the same
way. Percentiles are exact, over the benchmark's own per-request clock, not
interpolated inside histogram buckets.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q`` quantile (0..1) of ``values`` by linear interpolation
    between the two nearest order statistics (numpy's default rule)."""
    data = sorted(float(v) for v in values)
    if not data:
        raise ValueError('percentile of no values')
    if not 0.0 <= q <= 1.0:
        raise ValueError(f'q={q} outside [0, 1]')
    pos = q * (len(data) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 0.5)
