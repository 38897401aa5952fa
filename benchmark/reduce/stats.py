"""Order statistics, one convention for every metric: nearest rank."""
from __future__ import annotations

import math


def quantile(values, pct):
    """The nearest-rank `pct`-th percentile of `values`; None when empty."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(pct / 100.0 * len(ordered)) - 1)]


def median(values):
    return quantile(values, 50)
