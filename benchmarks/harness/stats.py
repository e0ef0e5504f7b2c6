"""Metric arithmetic. The percentile is linearly interpolated between
order statistics (numpy's default, ``bench.py``'s too): at 50 samples a
nearest-rank tail jumps from one order statistic to the next."""
import math


def percentile(values, q):
    """``q`` in [0, 100] over the ``values`` that are not None; None
    where there is nothing to read."""
    xs = sorted(v for v in values if v is not None)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50.0)
