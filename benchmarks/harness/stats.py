"""Window arithmetic: nearest-rank percentiles, whole-window rates and
the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics


def percentile_nearest_rank(values, pct: float) -> float:
    """The smallest value with at least ``pct`` % of the sample at or
    below it. Over fewer than 20 values the 95th is the maximum."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(pct / 100.0 * len(ordered)))
    return ordered[rank - 1]


def window_rate(units_completed: float, window_s: float) -> float:
    """Work completed over the WHOLE window (start of the first job to
    return of the last), stalls and all."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return units_completed / window_s


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
