"""The scrambled zipfian against its CDF."""
import numpy as np

from bench.zipf import ScrambledZipfian


def test_ranks_follow_the_zipfian_cdf():
    n, theta = 10_000, 0.99
    z = ScrambledZipfian(n, theta, np.random.default_rng(1))
    ranks = z.ranks(np.random.default_rng(2).random(400_000))
    emp = np.cumsum(np.bincount(ranks, minlength=n)) / len(ranks)
    w = 1.0 / np.arange(1, n + 1) ** theta
    cdf = np.cumsum(w) / w.sum()
    assert np.abs(emp - cdf).max() < 0.005  # Kolmogorov-Smirnov distance
    assert abs(np.mean(ranks == 0) - cdf[0]) < 0.003


def test_hot_items_are_scrambled_over_the_key_space():
    n = 10_000
    z = ScrambledZipfian(n, 0.99, np.random.default_rng(3))
    items = z.sample(np.random.default_rng(4), 200_000)
    counts = np.bincount(items, minlength=n)
    hot = np.argsort(-counts)[:10]
    assert set(hot.tolist()) == set(z.perm[:10].tolist())
    # not the lowest record numbers, as the unscrambled sampler gives
    assert not set(hot.tolist()) <= set(range(100))
    assert items.min() >= 0 and items.max() < n
