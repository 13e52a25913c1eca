"""Order statistics over the readings of one window. Nearest rank, no
interpolation and no rounding: a value printed is a value measured."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """The q-quantile (0 < q <= 1) of xs by nearest rank."""
    if not xs:
        raise ValueError("percentile of no readings")
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def median(xs: Sequence[float]) -> float:
    return percentile(xs, 0.5)
