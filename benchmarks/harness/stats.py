"""The few statistics the benchmark reports, in one place."""

import statistics
from typing import Optional, Sequence


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def percentile(values: Sequence[float], pct: int) -> Optional[float]:
    """The pct-th percentile (a whole number of percent, 1..99), linear
    between the two nearest order statistics. One value is its own
    percentile; none gives None."""
    if not values:
        return None
    if len(values) == 1:
        return float(values[0])
    return statistics.quantiles(values, n=100, method="inclusive")[pct - 1]


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median: the contract's measure for a bound (``statistics.quantiles``
    with n=4, its default exclusive method)."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
