"""Open-loop arrival schedules.

Every seed gets the same multiset of gaps between arrivals (the
quantiles of the exponential distribution at the rate), in an order drawn
from the seed: Poisson-like arrivals whose count and total length do not
change with the seed.
"""

from __future__ import annotations

import numpy as np


def poisson_offsets(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds), in seconds from the window's start, of
    ``round(rate * seconds)`` arrivals."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng.shuffle(gaps)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps) - gaps


def stepped_offsets(rates, seconds: float, rng):
    """Due times of a run that steps through ``rates``, each for an equal
    share of ``seconds``; returns (offsets, step index of each)."""
    step = seconds / len(rates)
    offs, which = [], []
    for k, rate in enumerate(rates):
        o = poisson_offsets(rate, step, rng)
        offs.append(k * step + o)
        which.append(np.full(len(o), k))
    return np.concatenate(offs), np.concatenate(which)
