"""Percentiles, and what each tail rests on."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of
    the sample at or below it. ``inf`` entries (requests that failed)
    sort last, so they push the tail out instead of vanishing."""
    if not values:
        return float('nan')
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def describe(name: str, values: Sequence[float], p: float) -> str:
    """The earlier-line report of one tail: what it rests on."""
    n = len(values)
    beyond = n - max(1, math.ceil(p / 100.0 * n)) if n else 0
    finite = [v for v in values if math.isfinite(v)]
    med = statistics.median(finite) if finite else float('nan')
    return (f'{name}: n={n} median={med:.3f} p{p:g}={percentile(values, p):.3f} '
            f'samples_beyond={beyond}')
