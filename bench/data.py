"""Seeded inputs of the benchmark: the rows of a deployment.

Everything here is a function of the run's `--seed` and of the numbers in a
configuration file, so the same seed gives the same inputs. The program
under test receives only what these functions return.
"""
from __future__ import annotations

import numpy as np

# numpy seeds a stream from any non-negative integer; a run's seed is taken
# modulo 2^64, and each stream below is split off by a fixed tag.
_TAGS = {"data": 0, "fit": 4, "warmup": 6}

# the share of a one-hot row's attributes that take its centre's category
ONEHOT_AFFINITY = 0.7


def rng_for(seed: int, tag: str) -> np.random.Generator:
    """An independent PCG64 stream per (seed, purpose)."""
    return np.random.default_rng([int(seed) % 2 ** 64, _TAGS[tag]])


def derived_seed(seed: int, tag: str, i: int) -> int:
    """A 63-bit seed for the i-th item of a run (e.g. the i-th fit)."""
    return int(np.random.default_rng([int(seed) % 2 ** 64, _TAGS[tag],
                                        int(i)])
               .integers(0, 2 ** 63 - 1))


def centres(rng: np.random.Generator, k: int, d: int) -> np.ndarray:
    """Cluster centres, uniform in [0, 1)^d (min-max-scaled features)."""
    return rng.random((k, d))


def blobs(rng: np.random.Generator, c: np.ndarray, n: int, noise: float,
          sparsity: float = 0.0, groups=None) -> np.ndarray:
    """n rows around the centres `c`: a uniformly drawn centre plus
    Gaussian noise. With `groups` (the sizes of one-hot attributes, in
    feature order) each row keeps exactly one feature per attribute: its
    centre's category for that attribute with probability
    `ONEHOT_AFFINITY`, else one drawn uniformly. Otherwise a
    Bernoulli(1 - sparsity) keep-mask per entry."""
    k, d = c.shape
    z = rng.integers(0, k, n)
    x = c[z]
    x += rng.normal(0.0, noise, (n, d))
    if groups:
        sizes = np.asarray(groups, np.int64)
        if sizes.sum() != d:
            raise ValueError(f"one-hot groups sum to {sizes.sum()}, not d={d}")
        offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        preferred = (rng.random((k, len(sizes))) * sizes).astype(np.int64)
        own = rng.random((n, len(sizes))) < ONEHOT_AFFINITY
        other = (rng.random((n, len(sizes))) * sizes).astype(np.int64)
        hot = offsets + np.where(own, preferred[z], other)
        mask = np.zeros((n, d), bool)
        mask[np.arange(n)[:, None], hot] = True
        x *= mask
    elif sparsity:
        x *= rng.random((n, d)) >= sparsity
    return x
