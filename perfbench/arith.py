"""Metric arithmetic: bytes on the bus, rates over all work and all
time, and percentiles over all calls.

The conventions are the IMB / nccl-tests ones: an allreduce of S bytes
per rank over n ranks puts 2(n-1)/n * S bytes per rank on the bus, and
a rate is the sum of the bytes of every call over the sum of their
times, never a mean of per-call rates nor a best time.
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def allreduce_bus_bytes(nbytes: int, nranks: int) -> float:
    """Bus bytes per rank of one allreduce of ``nbytes`` per rank."""
    return 2.0 * (nranks - 1) / nranks * nbytes


def rate(nbytes: Sequence[float], seconds: Sequence[float]) -> float:
    """Sum of bytes over sum of seconds."""
    total = math.fsum(seconds)
    if total <= 0.0:
        raise ValueError("no time to divide by")
    return math.fsum(nbytes) / total


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 1]) over every value given."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return float(ordered[rank - 1])


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values))


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, with the
    quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
