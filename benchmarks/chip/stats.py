"""Order statistics the benchmark reports, kept with the benchmark.

``pct`` is the nearest-rank percentile, the arithmetic of the farm's own
telemetry (``farm/telemetry.py _pct``), copied here so that no change to
the program moves the yardstick.
"""
from __future__ import annotations

import math
from typing import Sequence


def pct(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q`` quantile (0 < q <= 1) of ``values``."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``: overlapping parts
    are counted once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
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


def merged(intervals) -> list:
    """``intervals`` merged into disjoint, sorted ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out
