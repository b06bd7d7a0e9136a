"""Percentile and spread arithmetic, in one place and in plain Python."""
import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest ranks, numpy's default. None for an empty list."""
    xs = sorted(values)
    if not xs:
        return None
    rank = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values):
    return statistics.median(values) if values else None


def quartile_spread(values):
    """Distance between the first and third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, as a share of the
    median: the spread the builder's instructions set bounds from. None
    under two values or at a median of 0."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    mid = statistics.median(values)
    return (q3 - q1) / abs(mid) if mid else None
