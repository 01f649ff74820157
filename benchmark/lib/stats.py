"""Percentile, tail and spread arithmetic of the yardstick.

Nearest-rank percentiles (the value at rank ceil(q/100 * n) of the sorted
sample), so a number can be checked by hand.  A failed request has no latency:
it is counted in ``n`` and sorts beyond every real value.
"""
from __future__ import annotations

import math
import statistics
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float, failed: int = 0,
               censored: Optional[float] = None) -> float:
    """The ``q``-th percentile of ``values`` plus ``failed`` samples that lie
    beyond all of them.  Where the rank falls among the failed, the answer is
    ``censored`` (the longest time any of them was known to have waited) and,
    with none given, infinity.  An empty sample has no percentile."""
    real = sorted(values)
    n = len(real) + failed
    if n == 0:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    rank = max(1, math.ceil(q / 100.0 * n))
    if rank <= len(real):
        return real[rank - 1]
    return math.inf if censored is None else max(censored, real[-1] if real
                                                   else censored)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile: the
    choosing-metrics guide wants ten or more before a tail is quoted."""
    return n - max(1, math.ceil(q / 100.0 * n))


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as the contract measures it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

