"""The arithmetic of the end-to-end metrics, in one place."""

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest ranks, as ``numpy.percentile`` does by default."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50.0)
