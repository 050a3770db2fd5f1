"""The benchmark's arithmetic on lists of times: kept here so that every
PR computes a tail the same way."""
import math
from statistics import median  # noqa: F401  (readers take it from here)


def percentile(values, q):
    """The `q`-th percentile (0 < q <= 100) by the nearest-rank rule: the
    smallest value with at least q% of the values at or below it. No
    interpolation, so the result is always a time that was measured:
    percentile([1..20], 95) is 19, percentile([1..200], 95) is 190 (ten
    beyond it)."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} is outside (0, 100]")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]
