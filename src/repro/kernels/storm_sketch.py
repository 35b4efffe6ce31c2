"""Pallas TPU kernels: fused SRP hash + histogram (the STORM insert hot loop).

A GPU implementation scatter-increments the ``R x B`` counter array with
atomics. TPUs have no fast scatter, so the insert is re-thought for the MXU/
VPU (DESIGN.md §3): stream data tiles HBM->VMEM, run the ``p`` projection
matmuls, sign+pack to codes, and count each bucket's hits over the batch tile
into a VMEM-resident histogram. Codes never touch HBM; each data element is
read exactly once.

Schedule (one kernel serves all four entry points):
  grid = (S, R/br, n/bn, d/bd); ``k`` (features) fastest, then ``n``.
  - scratch ``acc (p, bn, br)`` accumulates projections over ``k``;
  - on the last ``k`` step the epilogue packs codes (masked rows get code
    -1, which no bucket matches) and, bucket by bucket, adds the tile's hit
    counts to a bucket-major int32 ``(B, br)`` histogram scratch. Rows of
    that scratch are lane-dense ``br``-wide vectors, so no value carries the
    small bucket axis in its lanes;
  - on the last ``(n, k)`` step the write-back epilogue casts the int32
    histogram to ``out_dtype`` — saturating at the dtype range for narrow
    counters (DESIGN.md §6/§12) — and stores the ``(B, br)`` output block
    ONCE. The wrapper transposes the bucket-major ``(S, B, R)`` result to the
    library's ``(S, R, B)`` layout.

The int32-scratch + one-``saturating_cast``-epilogue split is what makes
narrow counter tiles (``out_dtype=int16/int8``) native: the accumulator can
never wrap mid-batch, the HBM output (and hence the resident bank) shrinks
2–4x, and the result is bit-identical to ``saturating_cast`` of the int32
histogram — the same widen/saturate discipline ``core/sketch.py`` owns.

``paired=True`` is the antithetic PRP insert (DESIGN.md §3.2): the augmented
pair ``aug(±z) = [±z, 0, pad]`` shares the padding coordinate, so the
epilogue derives the negative-side projections from the accumulator and a
rank-1 ``pad ⊗ w_pad`` correction — both code sets from one projection pass,
halving MXU flops and HBM reads per insert versus two single-sided calls.

The sketch axis ``S`` leads the grid (DESIGN.md §10): ``(S, n, d)``-stacked
tenant batches produce an ``(S, R, B)`` counter stack in ONE launch. The hash
family is shared across the bank, so the weight blocks are reused unchanged
for every ``s``; slice ``s`` of the result is the lone-sketch output for
tenant ``s``, tile for tile. The lone-sketch entry points run the same
kernel at ``S = 1``.

The projection matmuls run at ``Precision.HIGHEST`` (f32 contraction on the
MXU) so kernel codes match the f32 reference hash, which pins the same
precision (``kernels/ref.py``, ``core/lsh.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lsh

Array = jax.Array

HIGHEST = jax.lax.Precision.HIGHEST


def match_vma(*operands):
    """Type kernel operands for ``jax.shard_map``'s varying-axes check.

    Returns the operands, each cast varying over every mesh axis any of
    them varies over (a replicated hash family beside a per-device data
    shard), and that axis set, which the ``pallas_call`` output must carry.
    Outside ``shard_map`` the set is empty and nothing changes.
    """
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))

    def cast(a):
        missing = tuple(vma - jax.typeof(a).vma)
        return jax.lax.pcast(a, missing, to="varying") if missing else a

    return [cast(a) for a in operands], vma


def _cast_out(hist32: Array, out_dtype) -> Array:
    """int32 histogram -> output dtype; clamps narrow dtypes at their range.

    Counters only grow, so one clamp at kernel-epilogue time equals clamping
    the exact total for this launch; callers that accumulate launches
    saturating-add the tiles (``core.sketch.saturating_add``), which keeps
    the composition exact too (DESIGN.md §12).
    """
    dtype = jnp.dtype(out_dtype)
    if dtype.itemsize >= 4:
        return hist32.astype(dtype)
    info = jnp.iinfo(dtype)
    return jnp.clip(hist32, info.min, info.max).astype(dtype)


def _insert_kernel(*refs, planes: int, paired: bool, tail: int,
                   n_steps: int, k_steps: int, out_dtype):
    if paired:
        x_ref, w_ref, wp_ref, o_ref, acc_ref, hist_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref, hist_ref = refs
    n_i = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when(jnp.logical_and(n_i == 0, k == 0))
    def _init_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)  # (bn, bd) — this sketch's data tile
    for j in range(planes):
        acc_ref[j, :, :] += jnp.dot(
            x, w_ref[j, :, :].astype(jnp.float32),
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # The last feature tile carries [pad, mask] (paired) or [mask] at
        # column ``tail`` (see _banked_insert).
        codes = jnp.zeros(acc_ref.shape[1:], jnp.int32)  # (bn, br)
        for j in range(planes):
            codes += (acc_ref[j, :, :] > 0).astype(jnp.int32) << j
        code_sets = [codes]
        if paired:
            pad = x[:, tail:tail + 1]  # (bn, 1)
            codes_n = jnp.zeros(acc_ref.shape[1:], jnp.int32)
            for j in range(planes):
                acc = acc_ref[j, :, :]  # proj(aug(z)) = s + t
                t2 = 2.0 * pad * wp_ref[j, :, :].astype(jnp.float32)
                codes_n += ((t2 - acc) > 0).astype(jnp.int32) << j
            code_sets.append(codes_n)  # proj(aug(-z)) = 2t - proj(aug(z))
        m_at = tail + 1 if paired else tail
        valid = x[:, m_at:m_at + 1] > 0  # (bn, 1)
        code_sets = [jnp.where(valid, c, -1) for c in code_sets]
        for b in range(hist_ref.shape[0]):
            hits = sum((c == b).astype(jnp.float32) for c in code_sets)
            hist_ref[b:b + 1, :] += jnp.sum(
                hits, axis=0, keepdims=True).astype(jnp.int32)

    @pl.when(jnp.logical_and(n_i == n_steps - 1, k == k_steps - 1))
    def _writeback():
        o_ref[0] = _cast_out(hist_ref[...], out_dtype)


def _banked_insert(x, w, mask, *, paired, block_n, block_r, block_d,
                   out_dtype, interpret):
    """``(S, n, d)`` stack -> ``(S, R, B)`` histograms (see module doc).

    The per-row scalars the epilogue needs — the PRP pad coordinate and the
    validity mask — ride as extra feature columns with zero hyperplane
    weight (adding exact zeros leaves every projection bit-identical), at
    the start of the last feature tile. A separate ``(S, n, 1)`` operand
    would be padded 128-fold in HBM, its minor axis filling a lane tile.
    """
    s, n, d = x.shape
    p, d_in, r = w.shape
    buckets = 1 << p
    mask = mask.astype(jnp.float32)
    if paired:
        assert d_in == d + 2, (d_in, d)
        z = x.astype(jnp.float32)
        pad = jnp.sqrt(jnp.clip(1.0 - lsh.row_sq_norm(z), 0.0, None))
        # aug(z) = [z, 0, pad], then the [pad, mask] tail.
        x, aug, tail = z, [jnp.zeros_like(pad), pad], [pad, mask]
    else:
        assert d == d_in, (d, d_in)
        aug, tail = [], [mask.astype(x.dtype)]
    # Columns are stacked from (S, n) rows, never built as (S, n, 1).
    width = d_in + len(tail)
    if width <= block_d:  # one feature tile: the tail follows the features
        bd, tail_at = width, d_in
        x = jnp.concatenate([x, jnp.stack(aug + tail, axis=-1)], axis=-1)
    else:  # the tail opens a last tile of its own
        bd, tail_at = block_d, 0
        if aug:
            x = jnp.concatenate([x, jnp.stack(aug, axis=-1)], axis=-1)
        x = jnp.concatenate([jnp.pad(x, ((0, 0), (0, 0), (0, (-d_in) % bd))),
                             jnp.stack(tail, axis=-1)], axis=-1)

    bn = min(block_n, max(8, n))
    br = min(block_r, r)
    n_pad, r_pad, d_pad = (-n) % bn, (-r) % br, (-x.shape[-1]) % bd
    xp = jnp.pad(x, ((0, 0), (0, n_pad), (0, d_pad)))  # pad rows masked out
    wp = jnp.pad(w, ((0, 0), (0, xp.shape[-1] - d_in), (0, r_pad)))
    operands = [xp, wp]
    in_specs = [
        pl.BlockSpec((1, bn, bd), lambda si, i, j, k: (si, j, k)),
        pl.BlockSpec((p, bd, br), lambda si, i, j, k: (0, k, i)),
    ]
    if paired:
        operands.append(jnp.pad(w[:, d_in - 1:d_in, :],
                                ((0, 0), (0, 0), (0, r_pad))))
        in_specs.append(pl.BlockSpec((p, 1, br),
                                     lambda si, i, j, k: (0, 0, i)))
    grid = (s, (r + r_pad) // br, (n + n_pad) // bn, xp.shape[-1] // bd)
    operands, vma = match_vma(*operands)

    out = pl.pallas_call(
        functools.partial(_insert_kernel, planes=p, paired=paired,
                          tail=tail_at, n_steps=grid[2], k_steps=grid[3],
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, buckets, br),
                               lambda si, i, j, k: (si, 0, i)),
        out_shape=jax.ShapeDtypeStruct((s, buckets, r + r_pad),
                                       jnp.dtype(out_dtype), vma=vma),
        scratch_shapes=[
            pltpu.VMEM((p, bn, br), jnp.float32),
            pltpu.VMEM((buckets, br), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return jnp.swapaxes(out, 1, 2)[:, :r]


_STATIC = ("block_n", "block_r", "block_d", "out_dtype", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def hash_histogram(
    x: Array,
    w: Array,
    mask: Array,
    *,
    block_n: int = 128,
    block_r: int = 256,
    block_d: int = 512,
    out_dtype=jnp.int32,
    interpret: bool = False,
) -> Array:
    """Fused hash+histogram. See ``ref.hash_histogram`` for semantics.

    Args:
      x: ``(n, d)`` pre-scaled (and, for asymmetric LSH, pre-augmented) points.
      w: ``(p, d, R)`` hyperplane normals.
      mask: ``(n,)`` validity mask in {0, 1} (stream padding).
      out_dtype: counter dtype of the output tile; narrow integer dtypes
        saturate at the dtype range (int32 scratch, one epilogue cast).

    Returns:
      ``(R, 2**p)`` counts in ``out_dtype``.
    """
    return _banked_insert(x[None], w, mask[None], paired=False,
                          block_n=block_n, block_r=block_r, block_d=block_d,
                          out_dtype=out_dtype, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def paired_hash_histogram(
    z: Array,
    w: Array,
    mask: Array,
    *,
    block_n: int = 128,
    block_r: int = 256,
    block_d: int = 512,
    out_dtype=jnp.int32,
    interpret: bool = False,
) -> Array:
    """Fused antithetic PRP insert. See ``ref.paired_hash_histogram``.

    Args:
      z: ``(n, d)`` pre-scaled points (``|z| <= 1``; NOT augmented).
      w: ``(p, d + 2, R)`` hyperplane normals for the augmented space.
      mask: ``(n,)`` validity mask in {0, 1} (stream padding).
      out_dtype: counter dtype of the output tile; narrow integer dtypes
        saturate at the dtype range (int32 scratch, one epilogue cast).

    Returns:
      ``(R, 2**p)`` counts in ``out_dtype`` (each unmasked point adds 2 per
      row, modulo saturation).
    """
    return _banked_insert(z[None], w, mask[None], paired=True,
                          block_n=block_n, block_r=block_r, block_d=block_d,
                          out_dtype=out_dtype, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def hash_histogram_banked(
    x: Array,
    w: Array,
    mask: Array,
    *,
    block_n: int = 128,
    block_r: int = 256,
    block_d: int = 512,
    out_dtype=jnp.int32,
    interpret: bool = False,
) -> Array:
    """Banked fused insert: S stacked histograms in one launch.

    Args:
      x: ``(S, n, d)`` pre-scaled points, sketch-major.
      w: ``(p, d, R)`` hyperplane normals (ONE hash family for the bank).
      mask: ``(S, n)`` validity mask in {0, 1} (ragged-stream padding).
      out_dtype: counter dtype of the output stack; narrow integer dtypes
        saturate at the dtype range (int32 scratch, one epilogue cast) and
        S-fold both the HBM result and the resident-bank footprint.

    Returns:
      ``(S, R, 2**p)`` counts in ``out_dtype``; slice ``s`` equals
      ``hash_histogram(x[s], w, mask[s], out_dtype=out_dtype)``.
    """
    return _banked_insert(x, w, mask, paired=False, block_n=block_n,
                          block_r=block_r, block_d=block_d,
                          out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def paired_hash_histogram_banked(
    z: Array,
    w: Array,
    mask: Array,
    *,
    block_n: int = 128,
    block_r: int = 256,
    block_d: int = 512,
    out_dtype=jnp.int32,
    interpret: bool = False,
) -> Array:
    """Banked fused antithetic PRP insert: S tenants in one launch.

    Args:
      z: ``(S, n, d)`` pre-scaled points (``|z| <= 1``; NOT augmented).
      w: ``(p, d + 2, R)`` hyperplane normals for the augmented space.
      mask: ``(S, n)`` validity mask in {0, 1} (ragged-stream padding).
      out_dtype: counter dtype of the output stack; narrow integer dtypes
        saturate at the dtype range (int32 scratch, one epilogue cast) and
        S-fold both the HBM result and the resident-bank footprint.

    Returns:
      ``(S, R, 2**p)`` counts in ``out_dtype``; slice ``s`` equals
      ``paired_hash_histogram(z[s], w, mask[s], out_dtype=out_dtype)``.
    """
    return _banked_insert(z, w, mask, paired=True, block_n=block_n,
                          block_r=block_r, block_d=block_d,
                          out_dtype=out_dtype, interpret=interpret)
