"""Locality-sensitive hash families used by STORM sketches.

The paper builds its surrogate losses from two LSH families:

* **SRP** (signed random projections) for angular distance, with collision
  probability ``(1 - acos(cos(x, y)) / pi) ** p`` for ``p`` concatenated
  hyperplanes.
* The **asymmetric inner-product hash** (Shrivastava & Li): augment data to
  ``[z, 0, sqrt(1 - |z|^2)]`` and queries to ``[q, sqrt(1 - |q|^2), 0]`` and
  apply SRP; the collision probability becomes monotone in the *unnormalized*
  inner product ``<q, z>`` (both augmented vectors are unit norm).
* **PRP** (paired random projections, the paper's contribution): hash both
  ``+z`` and ``-z`` under the same SRP function; the summed collision
  probability is the convex regression surrogate of Theorem 2.

Everything here is pure JAX and shape-polymorphic over leading batch dims.
Codes are ``int32`` in ``[0, 2**p)``; hash parameters are a simple pytree so
they can be donated/sharded like any other model state.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LSHParams:
    """Parameters of ``R`` independent p-plane SRP hash functions.

    Attributes:
      projections: ``(R, p, dim)`` float32 — Gaussian hyperplane normals.
    """

    projections: Array

    @property
    def rows(self) -> int:
        return self.projections.shape[0]

    @property
    def planes(self) -> int:
        return self.projections.shape[1]

    @property
    def dim(self) -> int:
        return self.projections.shape[2]

    @property
    def buckets(self) -> int:
        return 1 << self.planes


def init_srp(
    key: Array, rows: int, planes: int, dim: int, orthogonal: bool = False
) -> LSHParams:
    """Draw ``rows`` independent p-plane SRP hash functions.

    ``orthogonal=True`` draws structured orthogonal directions (Haar blocks,
    Choromanski et al.): hyperplanes are orthogonalized in blocks of up to
    ``dim`` across the flattened (row, plane) axis. SRP only depends on the
    *direction* of each hyperplane, so the marginal collision probability is
    unchanged while plane-level estimator errors become negatively correlated
    — a pure variance reduction (beyond-paper; see EXPERIMENTS.md §Perf-core).
    """
    if not orthogonal:
        w = jax.random.normal(key, (rows, planes, dim), dtype=jnp.float32)
        return LSHParams(projections=w)
    # One independent orthogonal pool per *plane index*: planes within a row
    # stay mutually independent (different pools), so the within-row product
    # collision probability k^p is unbiased; the same plane index across rows
    # is orthogonalized in blocks of `dim`, which only reduces variance.
    n_blocks = -(-rows // dim)
    g = jax.random.normal(key, (planes, n_blocks, dim, dim), dtype=jnp.float32)
    q, _ = jnp.linalg.qr(g)  # Haar-distributed orthonormal rows per block
    w = q.reshape(planes, n_blocks * dim, dim)[:, :rows]  # (p, R, d)
    return LSHParams(projections=jnp.swapaxes(w, 0, 1))


def _bit_weights(planes: int) -> Array:
    return (2 ** jnp.arange(planes, dtype=jnp.int32)).astype(jnp.int32)


def srp_codes(params: LSHParams, x: Array) -> Array:
    """Hash ``x`` with every row's SRP function.

    Args:
      params: ``LSHParams`` with projections ``(R, p, dim)``.
      x: ``(..., dim)`` points.

    Returns:
      ``(..., R)`` int32 bucket codes in ``[0, 2**p)``.
    """
    # (..., dim) @ (dim, R*p) -> (..., R, p): one matmul for all rows/planes.
    r, p, d = params.projections.shape
    w = params.projections.reshape(r * p, d)
    proj = jnp.einsum("...d,kd->...k", x.astype(jnp.float32), w,
                      precision=jax.lax.Precision.HIGHEST)
    bits = (proj.reshape(x.shape[:-1] + (r, p)) > 0).astype(jnp.int32)
    return jnp.einsum("...rp,p->...r", bits, _bit_weights(p))


def row_sq_norm(x: Array) -> Array:
    """``sum(x * x, axis=-1)`` by a pairwise tree of elementwise adds.

    A reduce's rounding depends on how XLA lays the batch out: on TPU the
    same rows sum differently when they are sliced from a fused host buffer
    than when they arrive alone, and on CPU when the batch size changes.
    The hashes below sign-test values built from these norms, so one ulp
    can move a point to another bucket. Elementwise adds in a fixed order
    round the same in every program.
    """
    sq = x * x
    while sq.shape[-1] > 1:
        if sq.shape[-1] % 2:
            sq = jnp.pad(sq, [(0, 0)] * (sq.ndim - 1) + [(0, 1)])
        sq = sq[..., 0::2] + sq[..., 1::2]
    return sq[..., 0]


def augment_data(z: Array) -> Array:
    """Asymmetric-LSH data augmentation ``z -> [z, 0, sqrt(1 - |z|^2)]``.

    Requires ``|z| <= 1`` (callers pre-scale the dataset); the norm residual is
    clipped at 0 for numerical safety.
    """
    pad = jnp.sqrt(jnp.clip(1.0 - row_sq_norm(z), 0.0, None))[..., None]
    zeros = jnp.zeros_like(pad)
    return jnp.concatenate([z, zeros, pad], axis=-1)


def augment_query(q: Array) -> Array:
    """Asymmetric-LSH query augmentation ``q -> [q, sqrt(1 - |q|^2), 0]``."""
    pad = jnp.sqrt(jnp.clip(1.0 - row_sq_norm(q), 0.0, None))[..., None]
    zeros = jnp.zeros_like(pad)
    return jnp.concatenate([q, pad, zeros], axis=-1)


def scale_to_unit_ball(
    z: Array, slack: float = 1.05, quantile: float = 0.9
) -> Tuple[Array, Array]:
    """Scale examples into the unit ball (asymmetric-LSH precondition).

    Scaling by the *max* norm crushes typical norms to ≪1, which concentrates
    every augmented point at the padding pole — per-row counts then degenerate
    to an all-or-nothing Bernoulli and estimator variance swamps the surrogate
    signal. We scale by a high *quantile* of the norms and project the outlier
    tail onto the sphere (usual practice for asymmetric inner-product LSH),
    keeping inner products O(1). Returns ``(scaled, scale)``.
    """
    norms = jnp.linalg.norm(z, axis=-1)
    c = jnp.quantile(norms, quantile) * slack + 1e-12
    zs = z / c
    nrm = jnp.linalg.norm(zs, axis=-1, keepdims=True)
    zs = zs / jnp.maximum(nrm, 1.0)  # clip the tail onto the unit sphere
    return zs, c


def normalize_query(q: Array) -> Array:
    """Scale a query onto the unit sphere (asymmetric hash needs ``|q| <= 1``).

    Zeros of ``<q, z>`` are invariant under this scaling, so the surrogate
    loss keeps the same minimizer (DESIGN.md §7).
    """
    nrm = jnp.sqrt(row_sq_norm(q))[..., None]
    return q / jnp.maximum(nrm, 1e-12)


# ---------------------------------------------------------------------------
# Analytic collision probabilities (the oracles the sketch estimates).
# ---------------------------------------------------------------------------


def srp_collision_prob(x: Array, y: Array, planes: int) -> Array:
    """P[SRP codes collide] for the symmetric (angular) hash."""
    cos = jnp.sum(x * y, axis=-1) / (
        jnp.linalg.norm(x, axis=-1) * jnp.linalg.norm(y, axis=-1) + 1e-12
    )
    cos = jnp.clip(cos, -1.0, 1.0)
    return (1.0 - jnp.arccos(cos) / jnp.pi) ** planes


def ip_collision_prob(inner: Array, planes: int) -> Array:
    """P[collision] of the asymmetric inner-product hash, ``inner in [-1, 1]``."""
    inner = jnp.clip(inner, -1.0, 1.0)
    return (1.0 - jnp.arccos(inner) / jnp.pi) ** planes


def prp_codes(params: LSHParams, z: Array) -> Tuple[Array, Array]:
    """Paired-random-projection codes for a data point ``z`` (pre-scaled).

    Inserts are performed at *both* returned code sets; the shared padding
    coordinate means ``aug(-z) != -aug(z)``, so both hashes are computed
    explicitly.

    Returns:
      ``(codes_pos, codes_neg)``, each ``(..., R)`` int32.
    """
    return srp_codes(params, augment_data(z)), srp_codes(params, augment_data(-z))


def query_codes(params: LSHParams, q: Array) -> Array:
    """Codes for a query vector (normalized then asymmetrically augmented)."""
    return srp_codes(params, augment_query(normalize_query(q)))


# ---------------------------------------------------------------------------
# Composition (Theorem 1): products of collision probabilities via injective
# code pairing. ``pair_codes(a, b)`` is injective on [0, Ba) x [0, Bb).
# ---------------------------------------------------------------------------


def pair_codes(codes_a: Array, codes_b: Array, buckets_b: int) -> Array:
    """Injective map Z x Z -> Z implementing LSH-composition (Thm 1).

    ``l(x) = pi(l1(x), l2(x))`` collides iff both constituents collide, so the
    composed collision probability is the product ``k1 * k2``.
    """
    return codes_a * buckets_b + codes_b


@partial(jax.jit, static_argnames=("planes",))
def empirical_collision_rate(
    params: LSHParams, x: Array, y: Array, planes: int
) -> Array:
    """Fraction of hash rows on which ``x`` and ``y`` collide (test helper)."""
    del planes  # implied by params; kept for symmetry with the analytic fns
    cx = srp_codes(params, x)
    cy = srp_codes(params, y)
    return jnp.mean((cx == cy).astype(jnp.float32), axis=-1)
