"""Fixed multisets for the generators: lengths at the mid-quantiles of their
distributions and gaps at the mid-quantiles of an exponential, so that a mix's
work does not depend on the luck of a draw."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal(n: int, median: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """n whole numbers at the mid-quantiles of a clipped lognormal."""
    inv = NormalDist().inv_cdf
    z = np.array([inv(float(q)) for q in _mid_quantiles(n)])
    return np.clip(np.rint(np.exp(math.log(median) + sigma * z)), lo, hi).astype(int)


def uniform(n: int, lo: float, hi: float) -> np.ndarray:
    return lo + (hi - lo) * _mid_quantiles(n)


def poisson_gaps(n: int, span_s: float) -> np.ndarray:
    """n gaps at the mid-quantiles of an exponential, scaled to fill span_s:
    the arrivals of a Poisson process of rate n / span_s with its count fixed."""
    g = -np.log1p(-_mid_quantiles(n))
    return g * (span_s / g.sum())


def mixture(n: int, parts: list[dict]) -> np.ndarray:
    """Lengths of a mixture of clipped lognormals, each part at its share."""
    counts = [int(round(p["share"] * n)) for p in parts]
    counts[0] += n - sum(counts)
    return np.concatenate([
        lognormal(c, p["median"], p["sigma"], p["min"], p["max"])
        for c, p in zip(counts, parts) if c > 0])
