"""The arithmetic of the end-to-end metrics and of the bounds."""

import math
import statistics
from typing import Sequence


def nearest_rank(values: Sequence[float], share: float) -> float:
    """The ``share`` quantile by nearest rank: the smallest value with
    at least ``share`` of the values at or below it."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(1, math.ceil(share * len(ordered))) - 1]


def per_minute(count: int, seconds: float) -> float:
    """Completed work over the whole window's time, per minute."""
    return 60.0 * count / seconds


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, as a share of
    the median (Python's ``statistics.quantiles``, as the driver takes
    it)."""
    first, _, third = statistics.quantiles(values, n=4)
    return (third - first) / statistics.median(values)
