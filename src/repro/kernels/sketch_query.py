"""Pallas TPU kernel: batched STORM sketch query (hash + gather + row-mean).

The DFO optimizer issues ~2k sphere queries per step and the quadratic-refine
polish issues ``3 * (1 + d + d(d+1)/2)`` trust-region samples in one batch;
this kernel fuses the query-side hashing with the counter gather so a whole
DFO step is one call. TPU has no fast gather either — the gather is a
bucket-by-bucket select against the counter tile held in VMEM.

Schedule (DESIGN.md §3.3):
  grid = (m/bm, R/br, d/bd); ``k`` (features) fastest, then ``R``.
  - scratch ``acc (p, bm, br)`` accumulates projections over ``k`` for the
    current (query-tile, row-tile) pair;
  - at the last ``k`` step, codes are packed and, for each bucket ``b``, the
    lane-dense ``(1, br)`` row of counts at ``b`` is selected where a
    query's code equals ``b``; the row-tile's partial sum is added to the
    output;
  - each output block (bm, 1) is revisited across the whole (R, d) subgrid
    and initialized once at the first step, so arbitrarily large query
    batches (m >> 128) stream through without a reference fallback.

Counters enter the kernel bucket-major, ``(B, S, R)`` (the wrapper
transposes the library's ``(S, R, B)``), so every tile the epilogue touches
carries the long ``R`` axis in its lanes and the small bucket axis never
does.

The banked variant (``sketch_query_banked``, DESIGN.md §9) serves S sketches
that share one hash family: the projection/code pipeline is untouched (one
matmul pass for all m points) and only the epilogue changes — each query row
one-hot selects its own table (``sel @ counts[b]``, an MXU contraction at
f32 precision, exact on integer counts) before the bucket select. The lone
query is the same kernel at ``S = 1``, where the select is the identity and
is skipped.

Tenant-major batches (``points_per_table=Q``: point ``i`` reads table
``i // Q``, the gateway's slot layout, DESIGN.md §10.2) take a routed fetch
instead: counters enter table-major, ``(S, B, R)``, and a query tile of
``bm = G·Q`` points fetches only its own ``(G, B, br)`` block, so the bank
is read once per call instead of once per query tile, and the epilogue
selects each table's rows with the same bucket-by-bucket ``where`` — no
S-wide one-hot, no MXU select. It engages when ``Q % 8 == 0``, which keeps
a table's rows whole sublane tiles; other callers (arbitrary ``sketch_idx``)
keep the one-hot kernel.

Counter tiles may be narrow (int16/int8, DESIGN.md §12): the epilogue lifts
the tile to f32 right at the gather, so a narrow bank streams S-fold less
VMEM per row tile and the result is bit-equal to querying the widened bank
— every narrow counter value (|c| ≤ 32767 < 2^24) is exact in float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.storm_sketch import match_vma

Array = jax.Array

HIGHEST = jax.lax.Precision.HIGHEST

# VMEM budget for one f32-sized (B, S, br) counter block: the row tile
# shrinks with S*B until the block fits. The block is double-buffered, so a
# large bank can still outgrow the 16 MiB of scoped VMEM a v5e grants by
# default; ``_vmem_limit`` then asks for what the kernel needs.
_COUNTER_BLOCK_BYTES = 4 << 20
_LANES = 128


def _project(q_ref, w_ref, o_ref, acc_ref, *, planes: int):
    """Accumulate the current query tile's projections over ``k``; zero the
    output block at its first visit."""
    j = pl.program_id(1)  # row (R) tile
    k = pl.program_id(2)  # feature (d) tile

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)  # (bm, bd)
    for p in range(planes):
        acc_ref[p, :, :] += jnp.dot(
            q, w_ref[p, :, :].astype(jnp.float32),
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )


def _codes(acc_ref, planes: int) -> Array:
    codes = jnp.zeros(acc_ref.shape[1:], jnp.int32)  # (bm, br)
    for p in range(planes):
        codes += (acc_ref[p, :, :] > 0).astype(jnp.int32) << p
    return codes


def _query_kernel(q_ref, w_ref, c_ref, idx_ref, o_ref, acc_ref, *,
                  planes: int, k_steps: int):
    _project(q_ref, w_ref, o_ref, acc_ref, planes=planes)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        buckets, s, _ = c_ref.shape
        codes = _codes(acc_ref, planes)
        if s > 1:
            # Per-query table select: (bm, S) one-hot against the sketch
            # axis, contracted with each bucket's (S, br) slab on the MXU.
            # Counts are integers, so the f32 contraction is exact.
            iota_s = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
            sel = (idx_ref[...] == iota_s).astype(jnp.float32)  # (bm, S)
        gathered = jnp.zeros(codes.shape, jnp.float32)
        for b in range(buckets):
            table = c_ref[b].astype(jnp.float32)  # (S, br)
            if s > 1:
                table = jnp.dot(sel, table, preferred_element_type=jnp.float32,
                                precision=HIGHEST)  # (bm, br)
            gathered += jnp.where(codes == b, table, 0.0)
        o_ref[...] += jnp.sum(gathered, axis=1, keepdims=True)


def _query_kernel_tenant_major(q_ref, w_ref, c_ref, o_ref, acc_ref, *,
                               planes: int, k_steps: int):
    _project(q_ref, w_ref, o_ref, acc_ref, planes=planes)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        tables, buckets, br = c_ref.shape  # the tile's own (G, B, br) block
        bm = acc_ref.shape[1]
        # Rows [gQ, (g+1)Q) read table g: split the sublane axis per table
        # (Q % 8 == 0 keeps each table's rows whole (8, 128) tiles) and
        # broadcast that table's (1, br) bucket row over them.
        codes = _codes(acc_ref, planes).reshape(tables, bm // tables, br)
        gathered = jnp.zeros(codes.shape, jnp.float32)
        for b in range(buckets):
            table = c_ref[:, pl.ds(b, 1), :].astype(jnp.float32)  # (G, 1, br)
            gathered += jnp.where(codes == b, table, 0.0)
        o_ref[...] += jnp.sum(gathered.reshape(bm, br), axis=1, keepdims=True)


def tenant_major(points_per_table: Optional[int]) -> bool:
    """Whether a batch with this static slot layout takes the routed fetch
    of ``_query_kernel_tenant_major`` (else the one-hot kernel)."""
    return points_per_table is not None and points_per_table % 8 == 0


def _tenant_major_tile(m: int, q: int, block_m: int):
    """``(tables, rows)`` of one query tile: ``G = rows // Q`` whole tables
    when ``Q <= block_m``, else one table split into tiles of the largest
    multiple of 8 that divides ``Q``."""
    if q <= block_m:
        g = min(block_m // q, -(-m // q))
        return g, g * q
    return 1, next(b for b in range(max(8, block_m // 8 * 8), 0, -8)
                   if q % b == 0)


def _row_block(block_r: int, r: int, s: int, buckets: int) -> int:
    """Row tile: at most ``block_r``, shrunk (in whole lane tiles) until the
    f32 ``(B, S, br)`` counter block fits ``_COUNTER_BLOCK_BYTES``."""
    fit = _COUNTER_BLOCK_BYTES // (4 * s * buckets) // _LANES * _LANES
    return min(block_r, r, max(_LANES, fit))


def _vmem_limit(s: int, buckets: int, br: int, itemsize: int):
    """Scoped VMEM to request: the default unless a huge bank's counter
    block (double-buffered, plus its f32 lift) outgrows it."""
    need = s * buckets * br * (2 * itemsize + 4) + (8 << 20)
    return need if need > (16 << 20) else None


@functools.partial(
    jax.jit, static_argnames=("points_per_table", "block_m", "block_r",
                              "block_d", "interpret")
)
def sketch_query_banked(
    q: Array,
    w: Array,
    counts: Array,
    sketch_idx: Optional[Array] = None,
    *,
    points_per_table: Optional[int] = None,
    block_m: int = 128,
    block_r: int = 512,
    block_d: int = 512,
    interpret: bool = False,
) -> Array:
    """Banked RACE query: per-point table select over a stacked counter bank.

    See ``ref.sketch_query_banked``. Give exactly one routing:
    ``sketch_idx`` (any table per point) runs the one-hot kernel, whose VMEM
    counter block is ``(B, S, br)`` — the row tile shrinks with ``S * B``
    (``_row_block``) and every query tile reads the whole bank;
    ``points_per_table=Q`` (point ``i`` reads table ``i // Q``, ``Q % 8 ==
    0``) runs the tenant-major kernel, whose ``(G, B, br)`` block holds only
    the tile's own tables. Narrow counter dtypes cut either block (and the
    HBM reads feeding it) 2–4x: the tile is loaded at its stored width and
    lifted to f32 only inside the epilogue gather, bit-equal to the widened
    bank.

    Args:
      q: ``(m, d)`` normalized/augmented query vectors; m is unrestricted.
      w: ``(p, d, R)`` hyperplane normals (one hash family for the bank).
      counts: ``(S, R, 2**p)`` stacked counters (int32/int16/int8).
      sketch_idx: ``(m,)`` int32 table index per query point.
      points_per_table: static tenant-major layout, ``ceil(m / Q) <= S``.

    Returns:
      ``(m,)`` float32 mean count over rows of each point's own table.
    """
    m, d = q.shape
    p, dw, r = w.shape
    s, _, buckets = counts.shape
    assert d == dw and counts.shape == (s, r, 1 << p)
    if (sketch_idx is None) == (points_per_table is None):
        raise ValueError("give exactly one of sketch_idx, points_per_table")
    if points_per_table is not None and not tenant_major(points_per_table):
        raise ValueError(f"points_per_table={points_per_table} is not a "
                         f"multiple of 8; route by sketch_idx instead")

    if points_per_table is None:
        bm = min(block_m, max(8, m))
        tables = s
    else:
        tables, bm = _tenant_major_tile(m, points_per_table, block_m)
    br = _row_block(block_r, r, tables, buckets)
    bd = min(block_d, d)
    m_pad, r_pad, d_pad = (-m) % bm, (-r) % br, (-d) % bd
    qp = jnp.pad(q, ((0, m_pad), (0, d_pad)))
    wp = jnp.pad(w, ((0, 0), (0, d_pad), (0, r_pad)))
    grid = ((m + m_pad) // bm, (r + r_pad) // br, (d + d_pad) // bd)
    # Padded R rows are zero and contribute 0; padded query rows are sliced
    # away below.
    if points_per_table is None:
        # Bucket-major counters; padded query rows read table 0.
        kernel, counter_spec = _query_kernel, pl.BlockSpec(
            (buckets, s, br), lambda i, j, k: (0, 0, j))
        cp = jnp.pad(jnp.transpose(counts, (2, 0, 1)),
                     ((0, 0), (0, 0), (0, r_pad)))
        extra = [jnp.pad(sketch_idx.astype(jnp.int32), (0, m_pad))[:, None]]
        extra_specs = [pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0))]
    else:
        # Table-major counters; tile i starts at table i·bm // Q. Padded
        # query rows read zero tables appended past the bank.
        rows_per_block = points_per_table * tables
        kernel, counter_spec = _query_kernel_tenant_major, pl.BlockSpec(
            (tables, buckets, br),
            lambda i, j, k: (i * bm // rows_per_block, 0, j))
        s_pad = max(0, -(-(m + m_pad) // rows_per_block) * tables - s)
        cp = jnp.pad(jnp.transpose(counts, (0, 2, 1)),
                     ((0, s_pad), (0, 0), (0, r_pad)))
        extra, extra_specs = [], []
    operands, vma = match_vma(qp, wp, cp, *extra)

    out = pl.pallas_call(
        functools.partial(kernel, planes=p, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((p, bd, br), lambda i, j, k: (0, k, j)),
            counter_spec,
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m + m_pad, 1), jnp.float32,
                                       vma=vma),
        scratch_shapes=[pltpu.VMEM((p, bm, br), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(
            tables, buckets, br, counts.dtype.itemsize)),
        interpret=interpret,
    )(*operands)
    return out[:m, 0] / r


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_r", "block_d", "interpret")
)
def sketch_query(
    q: Array,
    w: Array,
    counts: Array,
    *,
    block_m: int = 128,
    block_r: int = 512,
    block_d: int = 512,
    interpret: bool = False,
) -> Array:
    """Batched RACE query, tiled over queries. See ``ref.sketch_query``.

    Args:
      q: ``(m, d)`` normalized/augmented query vectors; m is unrestricted.
      w: ``(p, d, R)`` hyperplane normals.
      counts: ``(R, 2**p)`` counters.

    Returns:
      ``(m,)`` float32 mean count over rows.
    """
    return sketch_query_banked(
        q, w, counts[None], jnp.zeros((q.shape[0],), jnp.int32),
        block_m=block_m, block_r=block_r, block_d=block_d,
        interpret=interpret,
    )
