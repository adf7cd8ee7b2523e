"""Percentiles and latency from due times."""

from __future__ import annotations

import math
from typing import Optional, Sequence

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default "linear" method):
    with the n sorted samples at ranks 0..n-1, the q-th percentile sits at
    fractional rank ``(n - 1) * q / 100`` and interpolates between its
    neighbours.  An infinite neighbour makes the result infinite.

    Copied from ``src/repro/serving/batching.py`` ``percentile`` so that
    the benchmark's arithmetic does not move with the program."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * (q / 100.0)
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]) or math.isinf(xs[lo]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latencies_from_due(due: Sequence[float],
                       done: Sequence[Optional[float]]) -> list:
    """Per-request latency in seconds, timed from the time the request was
    due on the open-loop schedule (not from when it was submitted).  A
    request with no completion time (failed, refused, expired or never
    finished) is infinitely late."""
    if len(due) != len(done):
        raise ValueError("due and done differ in length")
    return [INF if t is None else float(t) - float(d)
            for d, t in zip(due, done)]

