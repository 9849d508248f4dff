"""Plain reference of the deployment's semantics, and the control.

`lloyd` is float64 Lloyd from given initial rows: assignment by the
smallest squared Euclidean distance, update by the mean of the assigned
rows, an empty cluster keeping its centroid. Nothing here imports the
program under test or takes anything it made.

`lloyd_fixed` is the control: the same reference computed in the precision
one step below the configuration's, a 32-bit ring with 10 fractional bits
in place of Z_2^64 with 20. A benchmark run never calls it; the tests and
`run.py --control` do, to show that the check in
`drivers/fit_stream.py` fails such a change.
"""
from __future__ import annotations

import numpy as np


def init_rows(seed: int, n: int, k: int) -> np.ndarray:
    """The initial-centroid rows of a fit seeded `seed`: k distinct rows
    drawn by numpy's PCG64 `default_rng(seed).choice(n, k, replace=False)`,
    the joint random-row initialisation the configuration states."""
    return np.random.default_rng(seed).choice(n, k, replace=False)


def dprime(x: np.ndarray, mu: np.ndarray) -> np.ndarray:
    """||mu_j||^2 - 2 x.mu_j: the squared distance less the row's own
    ||x||^2, which the argmin does not need."""
    return (mu * mu).sum(1)[None, :] - 2.0 * (x @ mu.T)


def _update(x, labels, mu):
    k = mu.shape[0]
    counts = np.bincount(labels, minlength=k)
    sums = np.zeros_like(mu)
    np.add.at(sums, labels, x)
    out = mu.copy()
    keep = counts > 0
    out[keep] = sums[keep] / counts[keep, None]
    return out


def lloyd(x: np.ndarray, init: np.ndarray, iters: int):
    """(centroids before each assignment, final centroids, final labels)."""
    mu = np.array(x[init], np.float64)
    hist = []
    labels = None
    for _ in range(iters):
        hist.append(mu)
        labels = dprime(x, mu).argmin(1)
        mu = _update(x, labels, mu)
    return hist, mu, labels


# --- the control: one precision step down ---------------------------------

CONTROL_BITS = 32
CONTROL_F = 10


def _fixed(v: np.ndarray, f: int = CONTROL_F) -> np.ndarray:
    return np.round(np.asarray(v, np.float64) * (1 << f)).astype(np.int64)


def _wrap(v: np.ndarray, bits: int = CONTROL_BITS) -> np.ndarray:
    """Reduce into the signed range of a `bits`-bit ring."""
    half = 1 << (bits - 1)
    return ((v + half) % (1 << bits)) - half


def lloyd_fixed(x: np.ndarray, init: np.ndarray, iters: int):
    """`lloyd` in fixed point: Z_2^32 words at 2^-10, distances at 2^-20,
    the update's division truncated to 2^-10."""
    xq = _fixed(x)
    mu = xq[init].copy()
    labels = None
    hist = []
    for _ in range(iters):
        hist.append(mu / float(1 << CONTROL_F))
        d = _wrap((mu * mu).sum(1)[None, :] - 2 * _wrap(xq @ mu.T))
        labels = (d >> CONTROL_F).argmin(1)
        counts = np.bincount(labels, minlength=mu.shape[0])
        sums = np.zeros_like(mu)
        np.add.at(sums, labels, xq)
        keep = counts > 0
        mu = mu.copy()
        mu[keep] = _wrap(sums[keep]) // counts[keep, None]
    return hist, mu / float(1 << CONTROL_F), labels
