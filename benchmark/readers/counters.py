"""Readers over what the driver counted in the window and the set-up. A
counter the driver did not report reads as None."""


def counter(r, name, scale=1.0):
    value = r.counters.get(name)
    return None if value is None else value * scale


def ratio(r, numerator, denominator, scale=1.0):
    """``scale * numerator / denominator`` of two counters."""
    top, bottom = r.counters.get(numerator), r.counters.get(denominator)
    if top is None or not bottom:
        return None
    return scale * top / bottom
