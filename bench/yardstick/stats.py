"""Metric arithmetic over a run's request log."""
from __future__ import annotations

import math

MISSING = math.inf  # the latency of a failed, shed or unanswered request


def latencies(requests) -> list:
    """Seconds from submit to answer per request; ``MISSING`` where any
    of its queries failed or was never answered.  ``requests``:
    (t_submit, t_done or None, ok) triples."""
    return [d - s if ok and d is not None else MISSING for s, d, ok in requests]


def percentile(values, q: float) -> float:
    """Nearest-rank q-quantile (0 < q <= 1) over every value, so a
    ``MISSING`` entry pushes the tail up instead of being dropped."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def rate(count: int, window_s: float) -> float:
    """Events per second over the whole window."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return count / window_s

