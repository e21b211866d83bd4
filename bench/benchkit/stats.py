"""Small statistics shared by the drivers."""

from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100); inf if any value is inf."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def sample(rng: np.random.Generator, n_total: int, k: int) -> list:
    """``min(k, n_total)`` distinct indices of ``n_total``, drawn from
    ``rng``, in order."""
    return sorted(int(i) for i in rng.permutation(n_total)[:k])
