"""Latency arithmetic shared by the metric readers and the tests."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation between the
    closest ranks (numpy's default rule); None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tpot_s(decode_s: float, n_tokens: int) -> Optional[float]:
    """Time per output token of one request: the time from its first token
    to its last, over the ``n - 1`` tokens after the first.  None for a
    request that made fewer than two tokens."""
    if n_tokens < 2:
        return None
    return decode_s / (n_tokens - 1)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles`` with n=4)."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / statistics.median(values)
