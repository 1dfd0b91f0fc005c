"""Open-loop request generation for serving mixes, from a mix's data file.

Arrivals: ``rate_rps * seconds`` requests whose gaps are the quantiles of
an exponential distribution at that rate (a Poisson process's gaps), in an
order drawn from the seed: every seed offers the same gaps, so a seed
changes when the load comes, not how much of it.  Seeds of a request:
``seeds_per_request`` distinct vertices, popularity by in-degree + 1 (as
``serving.scheduler.zipf_workload`` weighs them), so concurrent requests
share hot neighbourhoods as traffic over a power-law graph does.
"""
from __future__ import annotations

import numpy as np


def due_times(rate_rps: float, seconds: float, seed: int) -> np.ndarray:
    """Due offsets (s) from the window's start, first at 0."""
    n = max(1, int(round(rate_rps * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_rps
    gaps = np.random.default_rng([seed, 6]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def popularity(col: np.ndarray, n_vertices: int) -> np.ndarray:
    pop = np.bincount(col, minlength=n_vertices).astype(np.float64) + 1.0
    return pop / pop.sum()


def request_seeds(pop: np.ndarray, k: int, count: int,
                  rng: np.random.Generator) -> list:
    """``count`` requests of ``k`` distinct vertices each, by popularity."""
    cdf = np.cumsum(pop)
    cdf /= cdf[-1]
    out = []
    for _ in range(count):
        got = np.empty(0, np.int64)
        while len(got) < k:
            draw = np.searchsorted(cdf, rng.random(2 * k), side="right")
            draw = np.minimum(draw, len(pop) - 1)
            got = np.concatenate([got, draw])
            _, first = np.unique(got, return_index=True)
            got = got[np.sort(first)]
        out.append(got[:k])
    return out
