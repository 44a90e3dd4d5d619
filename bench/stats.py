"""Statistics of the benchmark: percentiles over all requests, and the
union and gaps of busy intervals."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank ``p``-th percentile of every value, misses included as
    ``inf``: the value at rank ceil(p/100 * n) of the sorted values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, start: float, end: float):
    """The ``(start, end)`` stretches of ``[start, end]`` that no interval
    covers, longest first."""
    out, cur = [], start
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, end)))
        cur = max(cur, e)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    out = [(s, e) for s, e in out if e > s]
    return sorted(out, key=lambda g: g[0] - g[1])
