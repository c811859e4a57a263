"""Percentile arithmetic of the benchmark's yardstick."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile by nearest rank: the smallest value with at
    least q% of the values at or below it. None for no values."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]
