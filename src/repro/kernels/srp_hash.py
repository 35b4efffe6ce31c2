"""Pallas TPU kernel: fused SRP hashing (matmul -> sign -> bit-pack).

Computes ``codes[i, r] = sum_j (x_i . w[j, :, r] > 0) << j`` for ``p`` planes.

Schedule (DESIGN.md §3):
  grid = (n/bn, R/br, d/bd) — ``k`` (the contraction over features) iterates
  fastest so each (i, j) output tile accumulates its ``p`` partial projections
  in a VMEM scratch accumulator; the sign + bit-pack epilogue runs once on the
  final ``k`` step and writes int32 codes. Projections never round-trip HBM.

  The ``p`` planes are plane-major in ``w`` so each grid step issues ``p``
  MXU matmuls of ``(bn, bd) @ (bd, br)`` — hardware-aligned when bn, br are
  multiples of 128 (p is tiny: 1..8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _srp_hash_kernel(x_ref, w_ref, o_ref, acc_ref, *, planes: int, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    for j in range(planes):  # p small & static -> unrolled MXU matmuls
        acc_ref[j, :, :] += jnp.dot(
            x, w_ref[j, :, :].astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        codes = jnp.zeros(o_ref.shape, jnp.int32)
        for j in range(planes):
            codes += (acc_ref[j, :, :] > 0).astype(jnp.int32) << j
        o_ref[...] = codes


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_r", "block_d", "interpret")
)
def srp_hash(
    x: Array,
    w: Array,
    *,
    block_n: int = 256,
    block_r: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> Array:
    """Fused SRP bucket codes. See ``ref.srp_hash`` for semantics.

    Args:
      x: ``(n, d)`` points; n, d need not be tile-aligned (padded here).
      w: ``(p, d, R)`` hyperplane normals.

    Returns:
      ``(n, R)`` int32 codes.
    """
    n, d = x.shape
    p, dw, r = w.shape
    assert d == dw, (d, dw)

    bn = min(block_n, max(8, n))
    br = min(block_r, r)
    bd = min(block_d, d)
    n_pad, r_pad, d_pad = (-n) % bn, (-r) % br, (-d) % bd
    # Zero-padding d is safe: zero features contribute 0 to every projection.
    xp = jnp.pad(x, ((0, n_pad), (0, d_pad)))
    wp = jnp.pad(w, ((0, 0), (0, d_pad), (0, r_pad)))
    grid = ((n + n_pad) // bn, (r + r_pad) // br, (d + d_pad) // bd)

    out = pl.pallas_call(
        functools.partial(_srp_hash_kernel, planes=p, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((p, bd, br), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bn, br), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, r + r_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((p, bn, br), jnp.float32)],
        interpret=interpret,
    )(xp, wp)
    return out[:n, :r]
