"""Order statistics the metric readers share."""

from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0 < q <= 1) by nearest rank: the smallest value
    with at least ``q`` of the sample at or below it."""
    if not values:
        raise ValueError("no values")
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]
