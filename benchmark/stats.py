"""Metric arithmetic: the statistics the end-to-end metrics are made of.

Quartiles and percentiles here are nearest-rank on the sorted sample, so a
statistic is always a value (or a mean of values) that was observed."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], pct: float) -> Optional[float]:
    """Nearest-rank percentile; None for an empty sample."""
    if not values:
        return None
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(pct / 100.0 * len(v)) - 1))]


def median(values: Sequence[float]) -> Optional[float]:
    if not values:
        return None
    v = sorted(values)
    n = len(v)
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


def band_mean(values: Sequence[float], lo_pct: float,
              hi_pct: float) -> Optional[float]:
    """Mean of the sorted sample's values from rank ceil(n*lo) up to (not
    including) rank ceil(n*hi): the band between two percentiles.  A band
    mean moves smoothly where a single high percentile sits on one sample
    and jumps between modes.  None when the band holds no sample."""
    v = sorted(values)
    lo = math.ceil(len(v) * lo_pct / 100.0)
    hi = math.ceil(len(v) * hi_pct / 100.0)
    band = v[lo:hi]
    return sum(band) / len(band) if band else None


def iqr_share(values: Sequence[float]) -> float:
    """Spread as the contract defines it: distance between the first and
    third quartile (statistics.quantiles, n=4) as a share of the median."""
    import statistics
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def gaps_ms(token_times: Sequence[float]) -> List[float]:
    return [(b - a) * 1e3 for a, b in zip(token_times, token_times[1:])]
