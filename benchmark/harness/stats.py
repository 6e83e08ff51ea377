"""Percentile and spread arithmetic (copied in spirit from
`mosaic_tpu.runtime.telemetry.summarize`: explicit nearest rank)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q*n``
    samples at or below it (``ceil(q*n) - 1`` on the sorted sample)."""
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    n = len(vals)
    return vals[min(n - 1, max(0, math.ceil(q * n) - 1))]


def iqr_share(values) -> float:
    """Distance between the first and third quartile
    (``statistics.quantiles(values, n=4)``) as a share of the median."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
