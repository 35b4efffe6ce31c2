"""Seeded request payloads and tenant draws, made before the window.

Rows are regression examples ``[x, y]`` drawn as ``datasets.make_regression``
draws them (a covariance of the configuration's condition number, a random
rotation, ``y = x @ theta + noise``), each column standardized and scaled
into the unit ball as ``launch/storm_serve.synth_traffic`` scales its rows
(``|z|`` about 0.4). Query points are Gaussian theta candidates. Both are
pooled: a request names a block of the pool, so the generator spends nothing
on data inside the window.

Every seed gets the same multiset of work: the same number of requests of
each kind, the same per-rank tenant counts under a Zipf law, and the same
set of inter-arrival gaps, in another order.
"""

from __future__ import annotations

import numpy as np

POOL_BLOCKS = 512


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def row_pool(rng, features: int, rows: int, noise: float, condition: float,
             blocks: int = POOL_BLOCKS) -> np.ndarray:
    """``(blocks, rows, features + 1)`` float32 ingest rows."""
    d = features
    eigs = np.logspace(0.0, np.log10(condition), d)
    eigs /= eigs.mean()
    rot, _ = np.linalg.qr(rng.normal(size=(d, d)))
    theta = rng.normal(size=d)
    x = (rng.normal(size=(blocks * rows, d)) * np.sqrt(eigs)) @ rot.T
    y = x @ theta + noise * rng.normal(size=blocks * rows)
    z = np.concatenate([x, y[:, None]], axis=1)
    z = (z - z.mean(0)) / z.std(0) * (0.4 / np.sqrt(d + 1))
    norm = np.linalg.norm(z, axis=1, keepdims=True)
    z /= np.maximum(norm / 0.99, 1.0)  # the unit ball, with room for rounding
    return z.astype(np.float32).reshape(blocks, rows, d + 1)


def theta_pool(rng, dim: int, points: int,
               blocks: int = POOL_BLOCKS) -> np.ndarray:
    """``(blocks, points, dim)`` float32 query thetas."""
    return rng.normal(size=(blocks, points, dim)).astype(np.float32)


def exact_counts(total: int, weights: np.ndarray) -> np.ndarray:
    """Integer counts summing to ``total`` in proportion to ``weights``
    (largest remainder), so every seed gets the same counts."""
    share = total * weights / weights.sum()
    counts = np.floor(share).astype(np.int64)
    rest = total - counts.sum()
    counts[np.argsort(counts - share, kind="stable")[:rest]] += 1
    return counts


def tenant_draws(rng, total: int, tenants: int, zipf_s: float) -> np.ndarray:
    """``total`` tenant ids: rank ``k`` (1-based) gets a share ``k**-s`` of
    the requests (``s = 0`` is uniform), ranks map to tenants by a seeded
    permutation, and the order is shuffled."""
    counts = exact_counts(total, np.arange(1, tenants + 1) ** -float(zipf_s))
    hot = rng.permutation(tenants)
    draws = np.repeat(hot, counts)
    rng.shuffle(draws)
    return draws.astype(np.int32)


def arrivals(rng, total: int, rate: float) -> np.ndarray:
    """Due times of ``total`` requests at ``rate`` per second: a fixed set of
    exponential gaps (their quantiles), shuffled."""
    q = (np.arange(total) + 0.5) / total
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    return np.cumsum(gaps)
