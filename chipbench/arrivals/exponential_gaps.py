"""Gaps of an open loop at a mean rate: the exponential law's quantiles,
in a seeded order.

Every seed gets the same ``n`` gaps (the quantiles at (k + 0.5) / n,
scaled so the last arrival falls one mean gap before the window closes),
shuffled: a Poisson process's shape without its seed-to-seed swing in the
amount of load.
"""
import numpy as np


def times(n: int, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times, seconds from the window's start, ascending."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    return np.cumsum(rng.permutation(gaps))
