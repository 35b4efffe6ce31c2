"""jit'd wrappers over the STORM Pallas kernels with backend dispatch.

On TPU the fused kernels run compiled; everywhere else (this CPU container,
unit tests) they run under ``interpret=True`` or fall back to the pure-jnp
reference — all three paths are numerically identical (integer counts), which
the kernel tests assert.

The weight layout here is the kernels' plane-major ``(p, d, R)``;
``from_lsh_params`` converts from the core library's ``(R, p, d)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import lsh, sketch as sketch_lib
from repro.kernels import ref
from repro.kernels import sketch_query as query_kernel
from repro.kernels import srp_hash as hash_kernel
from repro.kernels import storm_sketch as histogram_kernel

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def from_lsh_params(params: lsh.LSHParams) -> Array:
    """Core-layout projections ``(R, p, d)`` -> kernel layout ``(p, d, R)``."""
    return jnp.transpose(params.projections, (1, 2, 0))


def srp_hash(x: Array, w: Array, mode: str = "auto") -> Array:
    """Bucket codes ``(n, R)``; ``mode`` in {auto, kernel, interpret, ref}."""
    if mode == "ref" or (mode == "auto" and not _on_tpu() and x.shape[-1] < 64):
        return ref.srp_hash(x, w)
    interpret = mode == "interpret" or (mode == "auto" and not _on_tpu())
    return hash_kernel.srp_hash(x, w, interpret=interpret)


def hash_histogram(
    x: Array, w: Array, mask: Optional[Array] = None, mode: str = "auto",
    out_dtype=jnp.int32,
) -> Array:
    """Fused insert: ``(R, B)`` histogram of codes over the masked batch.

    ``out_dtype`` selects the counter tile dtype. Narrow dtypes (int16/int8)
    accumulate in int32 scratch and saturating-cast once in the epilogue —
    bit-equal to casting the int32 histogram (DESIGN.md §12).
    """
    if mask is None:
        mask = jnp.ones((x.shape[0],), jnp.float32)
    if mode == "ref" or (mode == "auto" and not _on_tpu() and x.shape[-1] < 64):
        return ref.hash_histogram(x, w, mask, out_dtype=out_dtype)
    interpret = mode == "interpret" or (mode == "auto" and not _on_tpu())
    return histogram_kernel.hash_histogram(x, w, mask, out_dtype=out_dtype,
                                           interpret=interpret)


def paired_hash_histogram(
    z: Array, w: Array, mask: Optional[Array] = None, mode: str = "auto",
    out_dtype=jnp.int32,
) -> Array:
    """Fused antithetic PRP insert: one projection pass, both code sets.

    ``z`` is pre-scaled but NOT augmented; ``w`` lives in the augmented space
    ``(p, d + 2, R)``. Equals ``hash_histogram(aug(z)) + hash_histogram(aug(-z))``
    at half the MXU flops and HBM reads. Narrow ``out_dtype`` tiles saturate
    once in the kernel epilogue.
    """
    if mask is None:
        mask = jnp.ones((z.shape[0],), jnp.float32)
    if mode == "ref" or (mode == "auto" and not _on_tpu() and z.shape[-1] < 64):
        return ref.paired_hash_histogram(z, w, mask, out_dtype=out_dtype)
    interpret = mode == "interpret" or (mode == "auto" and not _on_tpu())
    return histogram_kernel.paired_hash_histogram(z, w, mask,
                                                  out_dtype=out_dtype,
                                                  interpret=interpret)


def hash_histogram_banked(
    x: Array, w: Array, mask: Optional[Array] = None, mode: str = "auto",
    out_dtype=jnp.int32,
) -> Array:
    """Banked fused insert: ``(S, R, B)`` histograms of an ``(S, n, d)`` stack.

    One shared hash family serves the whole bank; slice ``s`` equals
    ``hash_histogram(x[s], w, mask[s], out_dtype)`` bit-for-bit.
    """
    if mask is None:
        mask = jnp.ones(x.shape[:2], jnp.float32)
    if mode == "ref" or (mode == "auto" and not _on_tpu() and x.shape[-1] < 64):
        return ref.hash_histogram_banked(x, w, mask, out_dtype=out_dtype)
    interpret = mode == "interpret" or (mode == "auto" and not _on_tpu())
    return histogram_kernel.hash_histogram_banked(x, w, mask,
                                                  out_dtype=out_dtype,
                                                  interpret=interpret)


def paired_hash_histogram_banked(
    z: Array, w: Array, mask: Optional[Array] = None, mode: str = "auto",
    out_dtype=jnp.int32,
) -> Array:
    """Banked fused antithetic PRP insert over an ``(S, n, dim)`` stack.

    The grid-over-S kernel (or vmapped reference) runs every tenant's
    projection pass in ONE launch; slice ``s`` equals
    ``paired_hash_histogram(z[s], w, mask[s], out_dtype)``.
    """
    if mask is None:
        mask = jnp.ones(z.shape[:2], jnp.float32)
    if mode == "ref" or (mode == "auto" and not _on_tpu() and z.shape[-1] < 64):
        return ref.paired_hash_histogram_banked(z, w, mask, out_dtype=out_dtype)
    interpret = mode == "interpret" or (mode == "auto" and not _on_tpu())
    return histogram_kernel.paired_hash_histogram_banked(z, w, mask,
                                                         out_dtype=out_dtype,
                                                         interpret=interpret)


def query_path(mode: str, dim: int,
               points_per_table: Optional[int] = None) -> str:
    """Which banked query a call runs: ``"ref"`` (the jnp oracle),
    ``"tenant_major"`` (the routed-fetch kernel, DESIGN.md §3.3) or
    ``"one_hot"``. ``dim`` is the augmented query width; the choice is
    static, so callers can record it at trace time."""
    if mode == "ref" or (mode == "auto" and not _on_tpu() and dim < 64):
        return "ref"
    if query_kernel.tenant_major(points_per_table):
        return "tenant_major"
    return "one_hot"


def sketch_query(
    q: Array,
    w: Array,
    counts: Array,
    mode: str = "auto",
    sketch_idx: Optional[Array] = None,
    points_per_table: Optional[int] = None,
) -> Array:
    """Batched RACE query: ``(m,)`` mean counts at the query codes.

    The kernel grids over query tiles, so any batch size (DFO sphere batches,
    quadratic-refine trust-region batches with m in the thousands) stays on
    the kernel path — there is no large-m reference fallback.

    With ``sketch_idx`` (``(m,)`` int32) the query is *banked*: ``counts`` is
    a ``(S, R, B)`` stack and point ``i`` gathers from table
    ``sketch_idx[i]`` — one fused call serves S tenants (DESIGN.md §9).
    ``points_per_table=Q`` (static) is the tenant-major special case, point
    ``i`` reads table ``i // Q``: it reads each table once per call where
    ``query_path`` says ``"tenant_major"``, and is the same one-hot query
    otherwise.
    """
    if sketch_idx is not None or points_per_table is not None:
        if counts.ndim != 3:
            raise ValueError(
                f"banked queries need (S, R, B) counts; got shape "
                f"{counts.shape}"
            )
        path = query_path(mode, q.shape[-1], points_per_table)
        if points_per_table is not None:
            if sketch_idx is not None:
                raise ValueError("give sketch_idx or points_per_table, "
                                 "not both")
            if -(-q.shape[0] // points_per_table) > counts.shape[0]:
                raise ValueError(
                    f"{q.shape[0]} points at {points_per_table} per table "
                    f"need more than the bank's {counts.shape[0]} tables"
                )
            if path != "tenant_major":
                sketch_idx = (jnp.arange(q.shape[0], dtype=jnp.int32)
                              // points_per_table)
                points_per_table = None
        if path == "ref":
            return ref.sketch_query_banked(q, w, counts, sketch_idx)
        interpret = mode == "interpret" or (mode == "auto" and not _on_tpu())
        return query_kernel.sketch_query_banked(
            q, w, counts, sketch_idx, points_per_table=points_per_table,
            interpret=interpret,
        )
    if counts.ndim != 2:
        raise ValueError(
            f"banked (S, R, B) counts need a sketch_idx; got shape "
            f"{counts.shape}"
        )
    if mode == "ref" or (mode == "auto" and not _on_tpu() and q.shape[-1] < 64):
        return ref.sketch_query(q, w, counts)
    interpret = mode == "interpret" or (mode == "auto" and not _on_tpu())
    return query_kernel.sketch_query(q, w, counts, interpret=interpret)


# ---------------------------------------------------------------------------
# High-level fused entry points mirroring repro.core.sketch
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("paired", "mode"))
def build_sketch(
    params: lsh.LSHParams,
    z: Array,
    mask: Optional[Array] = None,
    paired: bool = True,
    mode: str = "auto",
) -> sketch_lib.Sketch:
    """One-shot fused sketch of pre-scaled data ``z`` (PRP when paired).

    The paired insert runs the projection matmuls exactly once per batch and
    derives both antithetic code sets from the shared accumulator
    (``paired_hash_histogram``) — not two single-sided histogram passes.
    """
    w = from_lsh_params(params)
    if mask is None:
        mask = jnp.ones((z.shape[0],), jnp.float32)
    if paired:
        counts = paired_hash_histogram(z, w, mask, mode=mode)
    else:
        counts = hash_histogram(z, w, mask, mode=mode)
    n = jnp.sum(mask).astype(jnp.int32)
    return sketch_lib.Sketch(counts=counts, n=n)


@functools.partial(jax.jit,
                   static_argnames=("paired", "mode", "points_per_table"))
def query_theta_with_weights(
    sk,
    w: Array,
    theta_tilde: Array,
    paired: bool = True,
    mode: str = "auto",
    sketch_idx: Optional[Array] = None,
    points_per_table: Optional[int] = None,
) -> Array:
    """Fused surrogate-risk estimate with pre-transposed kernel weights.

    ``w`` is the plane-major ``(p, d, R)`` layout from :func:`from_lsh_params`.
    Sessions that issue many queries against one frozen hash (a ``fit`` run's
    scanned DFO steps, a serve loop) convert the layout ONCE and thread ``w``
    through their loss closure, so no ``(R, p, d) -> (p, d, R)`` transpose
    appears inside the per-step trace (asserted at jaxpr level in tests).
    ``core.fleet.make_loss_fn`` is the canonical builder of such sessions —
    PRP regression/probe losses with ``paired=True``, the single-sided
    classification margin loss with ``paired=False`` (the ``2^p`` Thm-3
    factor is applied by the caller on top of this estimate).

    ``sk`` may be a :class:`~repro.core.sketch.SketchBank` instead of a
    single :class:`~repro.core.sketch.Sketch`; then ``sketch_idx`` (``(m,)``
    int32, one entry per 2-D ``theta_tilde`` row) routes each point to its
    table and the estimator denominator is that sketch's own ``n`` — one
    fused ``F·(2k+1)``-point call serves many tenants (DESIGN.md §9). A
    caller whose batch is tenant-major gives the static
    ``points_per_table=Q`` instead (row ``i`` reads table ``i // Q``), which
    lets the kernel fetch each table once (:func:`sketch_query`).
    """
    banked = isinstance(sk, sketch_lib.SketchBank)
    routed = sketch_idx is not None or points_per_table is not None
    if banked != routed:
        raise ValueError("sketch_idx or points_per_table must be given iff "
                         "sk is a SketchBank")
    q = lsh.augment_query(lsh.normalize_query(theta_tilde))
    if banked:
        if theta_tilde.ndim != 2:
            raise ValueError("banked queries need a (m, dim) theta batch")
        mean_count = sketch_query(q, w, sk.counts, mode=mode,
                                  sketch_idx=sketch_idx,
                                  points_per_table=points_per_table)
        if sketch_idx is None:
            sketch_idx = (jnp.arange(q.shape[0], dtype=jnp.int32)
                          // points_per_table)
        n_per = sk.n[sketch_idx]
    else:
        mean_count = sketch_query(jnp.atleast_2d(q), w, sk.counts, mode=mode)
        n_per = sk.n
    denom = jnp.maximum(n_per.astype(jnp.float32), 1.0) * (
        2.0 if paired else 1.0
    )
    est = mean_count / denom
    return est[0] if theta_tilde.ndim == 1 else est


@functools.partial(jax.jit, static_argnames=("paired", "mode"))
def query_theta(
    sk: sketch_lib.Sketch,
    params: lsh.LSHParams,
    theta_tilde: Array,
    paired: bool = True,
    mode: str = "auto",
) -> Array:
    """Fused surrogate-risk estimate at a batch of parameters ``(m, d)``.

    One-shot convenience: converts the weight layout per call. Hot loops
    should hoist the conversion via :func:`query_theta_with_weights`.
    """
    return query_theta_with_weights(
        sk, from_lsh_params(params), theta_tilde, paired=paired, mode=mode
    )


@functools.partial(jax.jit, static_argnames=("batch", "paired", "mode", "dtype"))
def sketch_stream(
    params: lsh.LSHParams,
    z: Array,
    mask: Optional[Array] = None,
    batch: int = 1024,
    paired: bool = True,
    mode: str = "auto",
    dtype=jnp.int32,
) -> sketch_lib.Sketch:
    """Streaming kernel engine: scan masked batches through the fused insert.

    The dataset is padded to a batch multiple and scanned with a carried
    ``(R, B)`` count accumulator, so each step is one fused histogram kernel
    call (paired or single-sided) instead of a hash + scatter-add — the kernel
    analogue of ``core.sketch.sketch_dataset`` (DESIGN.md §3.4). Counts agree
    with the scatter-add scan up to floating-point sign ties in the paired
    projection (row masses exact; DESIGN.md §3.2).

    With a narrow ``dtype`` the carry AND the per-step kernel tiles live at
    that width — the device never materializes an int32 bank — and the
    saturating carry add keeps the result bit-equal to clamping the int32
    stream once at the end (``core.sketch.saturating_add``).
    """
    n, dim = z.shape
    w = from_lsh_params(params)
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    mask = mask.astype(jnp.float32)
    n_pad = (-n) % batch
    zp = jnp.concatenate([z, jnp.zeros((n_pad, dim), z.dtype)], axis=0)
    mp = jnp.concatenate([mask, jnp.zeros((n_pad,), jnp.float32)], axis=0)
    zb = zp.reshape(-1, batch, dim)
    mb = mp.reshape(-1, batch)

    def step(counts: Array, xs):
        z_t, m_t = xs
        if paired:
            tile = paired_hash_histogram(z_t, w, m_t, mode=mode,
                                         out_dtype=dtype)
        else:
            tile = hash_histogram(z_t, w, m_t, mode=mode, out_dtype=dtype)
        return sketch_lib.saturating_add(counts, tile), None

    init = jnp.zeros((params.rows, params.buckets), jnp.dtype(dtype))
    counts, _ = jax.lax.scan(step, init, (zb, mb))
    return sketch_lib.Sketch(counts=counts, n=jnp.sum(mask).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("batch", "paired", "mode", "dtype"))
def sketch_insert_banked(
    params: lsh.LSHParams,
    zs: Array,
    mask: Optional[Array] = None,
    batch: int = 1024,
    paired: bool = True,
    mode: str = "auto",
    dtype=jnp.int32,
) -> sketch_lib.SketchBank:
    """Fused banked insert: sketch S tenant streams in one kernel stream.

    The ingest half of the serving gateway (DESIGN.md §10): an ``(S, n, dim)``
    sketch-major stack (ragged tenants mask-padded to a common ``n``) scans
    through the banked fused histogram — each step is ONE grid-over-S kernel
    launch (or vmapped reference call) producing an ``(S, R, B)`` tile, so the
    bank ingests like ``sketch_stream`` ingests a single stream: no host loop
    over tenants, each data element read exactly once. Masked rows are hashed
    but contribute nothing; per-tenant ``n`` is the mask mass.

    Slice ``s`` of the result is bit-identical to
    ``sketch_stream(params, zs[s], mask[s], batch=batch, paired=paired)`` —
    the batch boundaries align (both pad up to a ``batch`` multiple), integer
    histogram tiles add exactly, and narrow dtypes saturate identically
    because per-batch saturating adds equal one final clamp.

    Args:
      params: hash parameters (ONE family shared by the whole bank).
      zs: ``(S, n, dim)`` pre-scaled tenant streams, sketch-major.
      mask: ``(S, n)`` validity mask in {0, 1}; ``None`` means all valid.
      batch: stream tile size.
      paired: PRP (regression/probes) vs single-sided inserts.
      mode: kernel dispatch (``auto | kernel | interpret | ref``).
      dtype: counter dtype; narrow dtypes keep the carry and the kernel
        tiles at that width (int32 accumulation stays in VMEM scratch).

    Returns:
      A :class:`~repro.core.sketch.SketchBank` with counts in ``dtype``.
    """
    s, n, dim = zs.shape
    w = from_lsh_params(params)
    if mask is None:
        mask = jnp.ones((s, n), jnp.float32)
    mask = mask.astype(jnp.float32)
    n_pad = (-n) % batch
    zp = jnp.concatenate([zs, jnp.zeros((s, n_pad, dim), zs.dtype)], axis=1)
    mp = jnp.concatenate([mask, jnp.zeros((s, n_pad), jnp.float32)], axis=1)
    # Scan over batch tiles (leading axis), keeping the bank axis inside the
    # fused call: (steps, S, batch, dim) so each step is one banked launch.
    zb = jnp.swapaxes(zp.reshape(s, -1, batch, dim), 0, 1)
    mb = jnp.swapaxes(mp.reshape(s, -1, batch), 0, 1)

    def step(counts: Array, xs):
        z_t, m_t = xs
        if paired:
            tile = paired_hash_histogram_banked(z_t, w, m_t, mode=mode,
                                                out_dtype=dtype)
        else:
            tile = hash_histogram_banked(z_t, w, m_t, mode=mode,
                                         out_dtype=dtype)
        return sketch_lib.saturating_add(counts, tile), None

    init = jnp.zeros((s, params.rows, params.buckets), jnp.dtype(dtype))
    counts, _ = jax.lax.scan(step, init, (zb, mb))
    return sketch_lib.SketchBank(
        counts=counts, n=jnp.sum(mask, axis=1).astype(jnp.int32)
    )
