"""Percentile and spread arithmetic, kept with the benchmark.

Nearest rank, the same rule as ``tpuic.metrics.meters.quantile``: a reported
percentile is a sample that was observed, never an interpolation.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``ceil(q/100 * n)``-th smallest sample (``q`` in percent).

    ``math.inf`` among the samples (an unanswered request) sorts last, so
    it shows in the tail it belongs to. An empty set raises: a made-up 0
    would read as a perfect latency."""
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    s = sorted(samples)
    if not s:
        raise ValueError("percentile of no samples")
    return s[max(1, math.ceil(q / 100.0 * len(s))) - 1]


def median(samples: Sequence[float]) -> float:
    """Mean of the two middle samples for an even count (the usual median;
    the nearest-rank p50 above is what latencies report)."""
    s = sorted(samples)
    if not s:
        raise ValueError("median of no samples")
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else 0.5 * (s[mid - 1] + s[mid])


def spread(samples: Sequence[float]) -> float:
    """Distance between the quartiles over the median: what the driver
    reads from a set of runs and holds a bound against."""
    q1, q3 = percentile(samples, 25), percentile(samples, 75)
    return (q3 - q1) / abs(median(samples))


def union_length(intervals: Sequence[tuple]) -> float:
    """Total length covered by ``(start, end)`` intervals, overlaps once."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals: Sequence[tuple]) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def subtract(intervals: Sequence[tuple], holes: Sequence[tuple]) -> list:
    """The parts of ``intervals`` that no interval of ``holes`` covers."""
    holes = merge(holes)
    out = []
    for s, e in merge(intervals):
        cur = s
        for hs, he in holes:
            if he <= cur:
                continue
            if hs >= e:
                break
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out
