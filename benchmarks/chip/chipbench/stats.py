"""Percentiles, kept with the benchmark so that no change to the program can
move them."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, ``q`` in [0, 100], over every sample."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    s = sorted(xs)
    k = min(len(s), max(1, math.ceil(q / 100.0 * len(s))))
    return float(s[k - 1])
