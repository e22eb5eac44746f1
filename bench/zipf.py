"""YCSB's scrambled zipfian item chooser.

The inverse-CDF sampler over a harmonic grid is copied from
``benchmarks/common.zipf_keys``; it puts rank 0 on item 0, so the hottest
items would be the lowest record numbers. YCSB's ``ScrambledZipfian``
spreads them over the key space; here a permutation drawn from the seed
does the same.
"""
from __future__ import annotations

import numpy as np


class ScrambledZipfian:
    def __init__(self, n_items: int, theta: float, rng: np.random.Generator):
        ranks = np.arange(1, n_items + 1, dtype=np.float64)
        cdf = np.cumsum(1.0 / ranks ** theta)
        self.cdf = cdf / cdf[-1]
        self.perm = rng.permutation(n_items)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """Zipfian ranks (0 = hottest) for uniform draws ``u`` in [0, 1)."""
        return np.minimum(np.searchsorted(self.cdf, u, side="right"),
                          len(self.cdf) - 1)

    def sample(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """Item indices in [0, n_items)."""
        return self.perm[self.ranks(rng.random(size))]
