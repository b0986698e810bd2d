"""Percentiles and rates as the benchmark defines them."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank q-th percentile (0 < q <= 100).  A failed request is
    passed as ``math.inf``: it counts as missing every limit."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return count / seconds
