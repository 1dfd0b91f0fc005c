"""Operations a configuration requires, counted from its sizes alone.

Each matrix product ``(m x k) @ (k x n)`` is ``2 m k n`` operations
forward and as many for its weight gradient; its input gradient counts
only where the input depends on trained weights (not for layer 1, whose
input is the features).  No padding and no deduplication enter the
count, so it reads the same work whatever implements it.
"""
from __future__ import annotations


def train_flops_per_seed(matmuls: list) -> float:
    return float(sum(2 * m * k * n * (3 if grad_in else 2)
                     for m, k, n, grad_in in matmuls))

