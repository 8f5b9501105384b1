"""Percentiles over every request, and the union of time intervals."""
import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values``, linearly interpolated
    between order statistics (numpy's default). Every value counts; an
    infinite one (a request that never finished) stays infinite."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == math.inf:
        return math.inf if pos > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def union_length(intervals, lo: float = -math.inf, hi: float = math.inf):
    """Total length covered by ``intervals`` ((start, end) pairs), clipped
    to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
