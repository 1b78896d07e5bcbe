"""Percentile and spread arithmetic (the nearest-rank percentile of
``tools/serve_bench.py``, kept here so the yardstick does not move)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of ``values`` (0 < q <= 100): the smallest
    sample with at least q% of the samples at or below it."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[rank - 1])


def supported_tail(n: int, want: float = 95.0) -> float:
    """The highest percentile, at most ``want``, that still has ten samples
    beyond it (choosing-metrics guide, section 1); below 20 samples the
    median is all a run supports."""
    if n < 20:
        return 50.0
    return min(want, 100.0 * (n - 10) / n)


def iqr_spread(values) -> float:
    """Distance between the quartiles as a share of the median, with the
    quartiles as ``statistics.quantiles(values, n=4)`` gives them (the
    builder's contract; numpy's lie closer together)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
