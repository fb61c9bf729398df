"""Nearest-rank percentiles, the one statistic the readers share."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The ``q``-th percentile (0 < q <= 100) by nearest rank: the
    smallest value with at least ``q`` percent of the sample at or below
    it. Infinite values sort last. None on an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]
