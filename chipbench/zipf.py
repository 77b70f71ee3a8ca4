"""Zipf draws over YCSB record numbers, with scrambled ranks.

The distribution is the one of ``benchmarks/common.zipf_keys`` (rank r
has weight r**-theta over ranks 1..n), copied here so that a change to the
program cannot move the benchmark.  The cumulative distribution is built
once per run, not once per draw, which matters at a million records.
Ranks go through a seeded permutation of the record numbers, as in
``benchmarks/store_phases.py``, so the hot records are spread over the
index instead of sitting at the low record numbers.
"""
from __future__ import annotations

import numpy as np


class ScrambledZipf:
    """Draws record numbers 1..n; the record of rank 0 is the hottest."""

    def __init__(self, n: int, theta: float, rng: np.random.Generator):
        weights = np.arange(1, n + 1, dtype=np.float64) ** (-theta)
        cdf = np.cumsum(weights)
        self.cdf = cdf / cdf[-1]
        self.key_of_rank = rng.permutation(n).astype(np.int64) + 1

    def keys(self, rng: np.random.Generator, size: int) -> np.ndarray:
        rank = np.searchsorted(self.cdf, rng.random(size), side="right")
        return self.key_of_rank[np.minimum(rank, len(self.cdf) - 1)]
