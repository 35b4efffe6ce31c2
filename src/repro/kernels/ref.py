"""Pure-jnp oracles for the STORM Pallas kernels.

Every kernel in this package is validated against these references with
``np.testing.assert_allclose`` across shape/dtype sweeps (see
``tests/test_kernels_*.py``). The references define the *semantics*; the
kernels define the *schedule*.

Weight layout convention (shared by kernels and refs): ``w: (p, d, R)`` —
plane-major so the kernel runs ``p`` MXU matmuls of ``(bn, bd) @ (bd, br)``
per tile instead of strided slicing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lsh
from repro.core.sketch import saturating_cast

Array = jax.Array


def _out_cast(counts32: Array, out_dtype) -> Array:
    """Define the narrow-tile semantics in ONE place: the int32 histogram
    saturating-cast to ``out_dtype`` (DESIGN.md §6/§12). The kernels'
    int32-scratch + epilogue-cast schedule must be bit-equal to this."""
    dtype = jnp.dtype(out_dtype)
    if dtype.itemsize >= 4:
        return counts32.astype(dtype)
    return saturating_cast(counts32, dtype)


def srp_hash(x: Array, w: Array) -> Array:
    """Signed-random-projection bucket codes.

    Args:
      x: ``(n, d)`` points.
      w: ``(p, d, R)`` hyperplane normals (plane-major layout).

    Returns:
      ``(n, R)`` int32 codes in ``[0, 2**p)``.
    """
    p = w.shape[0]
    codes = jnp.zeros((x.shape[0], w.shape[2]), jnp.int32)
    for j in range(p):
        proj = jnp.dot(x.astype(jnp.float32), w[j].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        codes = codes + ((proj > 0).astype(jnp.int32) << j)
    return codes


# Row-chunk scatters so the counter table stays cache-resident (~512 KB of
# int32 cells); big pair histograms (B*B buckets) are 1.4-2.2x faster chunked.
_SCATTER_MAX_CELLS = 131072


def _masked_histogram(codes: Array, mask: Array, buckets: int) -> Array:
    """Histogram of ``(n, R)`` codes over the masked batch -> ``(R, B)``.

    Flat 1-D scatter-add — 2-3x faster than the one-hot einsum on CPU at
    bench shapes (integer adds commute, so the counts are identical); the
    TPU kernels keep the one-hot reduction, which is the MXU-friendly form.
    Rows are processed in cache-sized chunks when the table is large.
    """
    r = codes.shape[1]
    rows_per = max(1, _SCATTER_MAX_CELLS // buckets)
    if r > rows_per:
        return jnp.concatenate(
            [
                _masked_histogram(codes[:, s : s + rows_per], mask, buckets)
                for s in range(0, r, rows_per)
            ],
            axis=0,
        )
    row_offset = (jnp.arange(r, dtype=jnp.int32) * buckets)[None, :]
    flat = jnp.zeros((r * buckets,), jnp.int32)
    idx = (row_offset + codes).reshape(-1)
    upd = jnp.broadcast_to(mask.astype(jnp.int32)[:, None], codes.shape).reshape(-1)
    return flat.at[idx].add(upd).reshape(r, buckets)


def hash_histogram(x: Array, w: Array, mask: Array,
                   out_dtype=jnp.int32) -> Array:
    """Fused hash + histogram: counts[r, b] = #{i : mask_i and code(x_i)_r == b}.

    Args:
      x: ``(n, d)`` points.
      w: ``(p, d, R)`` hyperplane normals.
      mask: ``(n,)`` {0,1} validity mask (stream padding).
      out_dtype: counter dtype; narrow dtypes saturate at the dtype range.

    Returns:
      ``(R, 2**p)`` counts in ``out_dtype``.
    """
    p = w.shape[0]
    codes = srp_hash(x, w)  # (n, R)
    return _out_cast(_masked_histogram(codes, mask, 1 << p), out_dtype)


def paired_srp_hash(z: Array, w: Array) -> tuple[Array, Array]:
    """Antithetic PRP codes with the projection matmuls run exactly once.

    The asymmetric-LSH augmentations of an antithetic pair share the padding
    coordinate: ``aug(z) = [z, 0, pad]`` and ``aug(-z) = [-z, 0, pad]`` with
    ``pad = sqrt(1 - |z|^2)``. Writing ``s = z . w_z`` and ``t = pad * w_pad``,

        proj(aug(z))  = s + t
        proj(aug(-z)) = t - s = 2t - proj(aug(z)),

    so one projection matmul plus a rank-1 correction yields both code sets
    (DESIGN.md §3.2). The positive-side codes are computed from the full
    augmented matmul, bit-identical to ``srp_hash(augment_data(z), w)``.

    Args:
      z: ``(n, d)`` pre-scaled points (``|z| <= 1``; NOT augmented).
      w: ``(p, d + 2, R)`` hyperplane normals for the augmented space.

    Returns:
      ``(codes_pos, codes_neg)``, each ``(n, R)`` int32.
    """
    return _paired_packed_codes(z, w, pos_shift=0, neg_shift=None)


def _paired_packed_codes(z: Array, w: Array, pos_shift, neg_shift):
    """Shared plane loop for the paired hash.

    With ``neg_shift=None`` returns ``(cpos, cneg)`` separately; with integer
    shifts returns one packed code ``sum_j pos_j << (j + pos_shift) +
    neg_j << (j + neg_shift)`` (the composed pair code, built in a single
    accumulator so the histogram path never materializes both code sets).
    """
    n, d = z.shape
    p, d_aug, r = w.shape
    assert d_aug == d + 2, (d_aug, d)
    z = z.astype(jnp.float32)
    pad = jnp.sqrt(jnp.clip(1.0 - lsh.row_sq_norm(z), 0.0, None))[:, None]
    za = jnp.concatenate([z, jnp.zeros_like(pad), pad], axis=-1)
    packed = neg_shift is not None
    if packed:
        cpair = jnp.zeros((n, r), jnp.int32)
    else:
        cpos = jnp.zeros((n, r), jnp.int32)
        cneg = jnp.zeros((n, r), jnp.int32)
    for j in range(p):
        # (n, R) — the only matmul pass
        acc = jnp.dot(za, w[j].astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
        t2 = 2.0 * pad * w[j, d + 1].astype(jnp.float32)[None, :]  # rank-1
        pos = (acc > 0).astype(jnp.int32)
        neg = (acc < t2).astype(jnp.int32)
        if packed:
            cpair = cpair + ((pos << (j + pos_shift)) + (neg << (j + neg_shift)))
        else:
            cpos = cpos + (pos << j)
            cneg = cneg + (neg << j)
    return cpair if packed else (cpos, cneg)


def paired_hash_histogram(z: Array, w: Array, mask: Array,
                          out_dtype=jnp.int32) -> Array:
    """Fused antithetic PRP insert: both code sets from one projection pass.

    Semantically equals ``hash_histogram(aug(z), w, mask) +
    hash_histogram(aug(-z), w, mask)`` while running the ``p`` projection
    matmuls once instead of twice.

    Args:
      z: ``(n, d)`` pre-scaled points (NOT augmented).
      w: ``(p, d + 2, R)`` hyperplane normals.
      mask: ``(n,)`` {0,1} validity mask.
      out_dtype: counter dtype; narrow dtypes saturate at the dtype range.

    Returns:
      ``(R, 2**p)`` counts in ``out_dtype`` (each unmasked point adds 2 per
      row, modulo saturation).
    """
    p = w.shape[0]
    buckets = 1 << p
    if buckets * buckets <= 4096:
        # One scatter pass over the composed pair code (the injective
        # ``lsh.pair_codes`` map, packed directly in the plane loop): each
        # point lands in one cell of the (R, B*B) pair histogram, and the
        # pos/neg histograms are its two marginals — halving scatter traffic
        # on top of the halved matmuls.
        cpair = _paired_packed_codes(z, w, pos_shift=p, neg_shift=0)
        pair = _masked_histogram(cpair, mask, buckets * buckets)
        pair = pair.reshape(-1, buckets, buckets)
        counts32 = (jnp.sum(pair, axis=2)
                    + jnp.sum(pair, axis=1)).astype(jnp.int32)
        return _out_cast(counts32, out_dtype)
    cpos, cneg = paired_srp_hash(z, w)
    counts32 = _masked_histogram(cpos, mask, buckets) + _masked_histogram(
        cneg, mask, buckets
    )
    return _out_cast(counts32, out_dtype)


def hash_histogram_banked(x: Array, w: Array, mask: Array,
                          out_dtype=jnp.int32) -> Array:
    """Banked fused insert oracle: S stacked histograms, one shared family.

    Args:
      x: ``(S, n, d)`` points, sketch-major.
      w: ``(p, d, R)`` hyperplane normals (shared across the bank).
      mask: ``(S, n)`` {0,1} validity mask (ragged-stream padding).
      out_dtype: counter dtype; narrow dtypes saturate at the dtype range.

    Returns:
      ``(S, R, 2**p)`` counts in ``out_dtype``; slice ``s`` is exactly
      ``hash_histogram(x[s], w, mask[s], out_dtype)`` (integer scatter-adds
      commute with the vmap batching, so the slices are bit-identical).
    """
    return jax.vmap(
        lambda xs, ms: hash_histogram(xs, w, ms, out_dtype)
    )(x, mask)


def paired_hash_histogram_banked(z: Array, w: Array, mask: Array,
                                 out_dtype=jnp.int32) -> Array:
    """Banked antithetic PRP insert oracle: S tenants, one projection pass each.

    Args:
      z: ``(S, n, d)`` pre-scaled points (NOT augmented), sketch-major.
      w: ``(p, d + 2, R)`` hyperplane normals (shared across the bank).
      mask: ``(S, n)`` {0,1} validity mask.
      out_dtype: counter dtype; narrow dtypes saturate at the dtype range.

    Returns:
      ``(S, R, 2**p)`` counts in ``out_dtype``; slice ``s`` is exactly
      ``paired_hash_histogram(z[s], w, mask[s], out_dtype)``.
    """
    return jax.vmap(
        lambda zs, ms: paired_hash_histogram(zs, w, ms, out_dtype)
    )(z, mask)


def sketch_query(q: Array, w: Array, counts: Array) -> Array:
    """Batched RACE gather: mean over rows of counts at the query codes.

    Args:
      q: ``(m, d)`` query vectors (already normalized/augmented).
      w: ``(p, d, R)`` hyperplane normals.
      counts: ``(R, 2**p)`` sketch counters.

    Returns:
      ``(m,)`` float32 — mean count over the R rows (caller normalizes by n).
    """
    codes = srp_hash(q, w)  # (m, R)
    rows = jnp.arange(counts.shape[0], dtype=jnp.int32)
    gathered = counts[rows[None, :], codes].astype(jnp.float32)  # (m, R)
    return jnp.mean(gathered, axis=-1)


def sketch_query_banked(
    q: Array, w: Array, counts: Array, sketch_idx: Array
) -> Array:
    """Banked RACE gather: each query point reads its own counter table.

    The hashing pass is shared (one projection matmul for all m points —
    the bank's sketches use ONE hash family); only the gather fans out over
    the ``S`` stacked tables. Point ``i`` equals
    ``sketch_query(q[i:i+1], w, counts[sketch_idx[i]])`` bit-for-bit.

    Args:
      q: ``(m, d)`` query vectors (already normalized/augmented).
      w: ``(p, d, R)`` hyperplane normals (shared across the bank).
      counts: ``(S, R, 2**p)`` stacked sketch counters.
      sketch_idx: ``(m,)`` int32 — which table each point gathers from.

    Returns:
      ``(m,)`` float32 — mean count over the R rows (caller normalizes by
      the per-sketch n).
    """
    codes = srp_hash(q, w)  # (m, R)
    rows = jnp.arange(counts.shape[1], dtype=jnp.int32)
    gathered = counts[
        sketch_idx[:, None], rows[None, :], codes
    ].astype(jnp.float32)  # (m, R)
    return jnp.mean(gathered, axis=-1)
