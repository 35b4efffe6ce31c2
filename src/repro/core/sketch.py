"""The STORM sketch: an ``R x B`` array of integer counters.

Insert: for each of the ``R`` rows, increment the bucket selected by that
row's LSH function. Query with parameter codes: average the counts at
``[r, code_r]`` over rows and divide by the number of inserts — an unbiased
estimate of the mean collision probability ``(1/n) sum_i k(theta, x_i)``
(RACE estimator).

PRP inserts touch *two* buckets per row (codes of ``+z`` and ``-z``), so the
PRP query divides by ``2n`` to estimate the mean surrogate loss
``g = (k_+ + k_-) / 2`` of Theorem 2.

The sketch is a pytree of two integer arrays, so merging is ``jnp.add`` and a
distributed merge is ``jax.lax.psum`` (see ``core/distributed.py``).

The pure-JAX update path here uses scatter-add; on TPU the fused Pallas
kernel (``repro.kernels.storm_sketch``) replaces hash+scatter with a
matmul + one-hot histogram held in VMEM (DESIGN.md §3). ``ops.py`` dispatches.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lsh

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Sketch:
    """STORM sketch state.

    Attributes:
      counts: ``(R, B)`` integer counters.
      n: scalar int32 — number of *logical* inserts (a PRP insert counts 1).
    """

    counts: Array
    n: Array

    @property
    def rows(self) -> int:
        return self.counts.shape[0]

    @property
    def buckets(self) -> int:
        return self.counts.shape[1]

    def memory_bytes(self) -> int:
        return self.counts.size * self.counts.dtype.itemsize + 4


def init_sketch(rows: int, buckets: int, dtype: jnp.dtype = jnp.int32) -> Sketch:
    """Zeroed sketch. ``dtype`` may be a narrow integer type (``int16``,
    ``uint16``, even ``int8``) — the paper's "tiny array of integer counters"
    footprint claim — in which case every insert path saturates at the dtype
    max instead of wrapping (DESIGN.md §6)."""
    return Sketch(
        counts=jnp.zeros((rows, buckets), dtype=dtype),
        n=jnp.zeros((), dtype=jnp.int32),
    )


def _row_ids(codes: Array) -> Array:
    # codes: (batch, R) -> row indices broadcast to the same shape.
    return jnp.broadcast_to(jnp.arange(codes.shape[-1], dtype=jnp.int32), codes.shape)


def _is_narrow(dtype) -> bool:
    return jnp.dtype(dtype).itemsize < 4


def saturating_cast(counts32: Array, dtype) -> Array:
    """Cast int32 counts to ``dtype``, clamping at the representable range.

    Counters only ever grow, so clamping per batch equals clamping the final
    total: once a cell pins at the max it stays there — the estimator's
    gathered count degrades gracefully (an undercount) instead of the
    catastrophic sign-flip of two's-complement wraparound.
    """
    info = jnp.iinfo(jnp.dtype(dtype))
    return jnp.clip(counts32, info.min, info.max).astype(dtype)


def _widen(counts: Array) -> Array:
    """Lift narrow counters to int32 so a batch of scatter-adds cannot wrap."""
    return counts.astype(jnp.int32) if _is_narrow(counts.dtype) else counts


def _narrow_back(counts32: Array, dtype) -> Array:
    return saturating_cast(counts32, dtype) if _is_narrow(dtype) else counts32


def saturating_add(counts: Array, tile: Array) -> Array:
    """Add a count tile into ``counts`` with widen/saturate discipline.

    Both operands are lifted to int32 before the add, and the result clamps
    back to ``counts.dtype``. Because increments are non-negative, chaining
    per-batch saturating adds is bit-identical to one final clamp of the
    exact int32 total (the monotone-saturation property ``saturating_cast``
    documents) — so streaming narrow-tile ingest matches the widened
    reference exactly, tile boundaries notwithstanding.
    """
    wide = _widen(counts) + _widen(tile)
    return _narrow_back(wide, counts.dtype)


def update(sketch: Sketch, codes: Array) -> Sketch:
    """Insert a batch of pre-hashed points.

    Args:
      sketch: current sketch.
      codes: ``(batch, R)`` int32 bucket codes.
    """
    dtype = sketch.counts.dtype
    wide = _widen(sketch.counts)
    wide = wide.at[_row_ids(codes), codes].add(jnp.ones((), wide.dtype))
    return Sketch(counts=_narrow_back(wide, dtype),
                  n=sketch.n + jnp.int32(codes.shape[0]))


def prp_update(sketch: Sketch, codes_pos: Array, codes_neg: Array) -> Sketch:
    """Paired insert: one logical point increments two buckets per row."""
    dtype = sketch.counts.dtype
    wide = _widen(sketch.counts)
    ones = jnp.ones((), wide.dtype)
    wide = wide.at[_row_ids(codes_pos), codes_pos].add(ones)
    wide = wide.at[_row_ids(codes_neg), codes_neg].add(ones)
    return Sketch(counts=_narrow_back(wide, dtype),
                  n=sketch.n + jnp.int32(codes_pos.shape[0]))


def insert(sketch: Sketch, params: lsh.LSHParams, x: Array) -> Sketch:
    """Hash-and-insert raw (already scaled) points ``x: (batch, dim)``."""
    return update(sketch, lsh.srp_codes(params, x))


def prp_insert(sketch: Sketch, params: lsh.LSHParams, z: Array) -> Sketch:
    """PRP hash-and-insert of pre-scaled concatenated examples ``[x, y]``."""
    cpos, cneg = lsh.prp_codes(params, z)
    return prp_update(sketch, cpos, cneg)


def merge(a: Sketch, b: Sketch) -> Sketch:
    """Mergeable-summary property: sketch of the union is the elementwise sum.

    Narrow counter dtypes widen to int32 for the add and saturate on the way
    back, matching ``update``/``prp_update`` — two near-full int16 shards
    must pin at the dtype max, not wrap to a negative count (DESIGN.md §6).
    """
    dtype = a.counts.dtype
    wide = _widen(a.counts) + _widen(b.counts)
    return Sketch(counts=_narrow_back(wide, dtype), n=a.n + b.n)


def query(sketch: Sketch, codes: Array, paired: bool = False) -> Array:
    """RACE estimate of the mean collision probability at the query codes.

    Args:
      sketch: the sketch.
      codes: ``(..., R)`` query codes.
      paired: True for PRP sketches (two increments per insert -> divide by 2n).

    Returns:
      ``(...,)`` float32 estimates in ``[0, buckets]`` (≈ ``[0, 1]`` for large n).
    """
    gathered = sketch.counts[_row_ids(codes), codes].astype(jnp.float32)
    mean_count = jnp.mean(gathered, axis=-1)
    denom = jnp.maximum(sketch.n.astype(jnp.float32), 1.0)
    if paired:
        denom = 2.0 * denom
    return mean_count / denom


def query_theta(
    sketch: Sketch, params: lsh.LSHParams, theta_tilde: Array, paired: bool = True
) -> Array:
    """Estimate the surrogate empirical risk at ``theta_tilde = [theta, -1]``."""
    return query(sketch, lsh.query_codes(params, theta_tilde), paired=paired)


# ---------------------------------------------------------------------------
# SketchBank: many sketches under ONE hash family, queried in one fused pass.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SketchBank:
    """A first-class bank of S sketches sharing one hash family (DESIGN.md §9).

    The serving-side unit of edge aggregation: per-tenant / per-shard counter
    tables stacked into one ``(S, R, B)`` gather target, so a single batched
    query with a per-point sketch index reads from S different tables in one
    pass. Everything that makes the lone :class:`Sketch` mergeable survives
    per-slice: ``bank.select(i)`` is an ordinary sketch, and
    :meth:`merge_groups` folds tenant groups by (saturating) counter addition.

    Attributes:
      counts: ``(S, R, B)`` integer counters — sketch-major stack.
      n: ``(S,)`` int32 — logical inserts per sketch.
    """

    counts: Array
    n: Array

    @property
    def size(self) -> int:
        return self.counts.shape[0]

    @property
    def rows(self) -> int:
        return self.counts.shape[1]

    @property
    def buckets(self) -> int:
        return self.counts.shape[2]

    def select(self, i: int) -> Sketch:
        """The i-th sketch as a standalone :class:`Sketch` view."""
        return Sketch(counts=self.counts[i], n=self.n[i])

    def merge_groups(self, assignment, num_groups: Optional[int] = None
                     ) -> "SketchBank":
        """Merge sketches into groups: ``out[g] = sum over {i: a_i == g}``.

        The bank analogue of :func:`merge` (gateway roll-up: collapse
        per-edge sketches into per-tenant ones). Narrow dtypes widen to
        int32 for the segment sum and saturate on the way back, like every
        other insert/merge path (DESIGN.md §6).

        Args:
          assignment: ``(S,)`` int group ids in ``[0, num_groups)``.
          num_groups: number of output sketches; defaults to
            ``max(assignment) + 1`` (requires a concrete assignment).
        """
        assignment = jnp.asarray(assignment, jnp.int32)
        g = (int(jnp.max(assignment)) + 1 if num_groups is None
             else num_groups)
        dtype = self.counts.dtype
        wide = jax.ops.segment_sum(_widen(self.counts), assignment,
                                   num_segments=g)
        return SketchBank(
            counts=_narrow_back(wide, dtype),
            n=jax.ops.segment_sum(self.n, assignment, num_segments=g),
        )

    def memory_bytes(self) -> int:
        return self.counts.size * self.counts.dtype.itemsize + 4 * self.size


def bank_of(sketches) -> SketchBank:
    """Stack standalone sketches (same shape/dtype) into a :class:`SketchBank`.

    The sketches must come from the SAME hash family — the bank stores no
    params, and the fused banked query hashes every point once with the
    shared ``LSHParams``; mixing hash draws would silently gather garbage.
    """
    sketches = list(sketches)
    if not sketches:
        raise ValueError("bank_of needs at least one sketch")
    shapes = {s.counts.shape for s in sketches}
    dtypes = {s.counts.dtype for s in sketches}
    if len(shapes) != 1 or len(dtypes) != 1:
        raise ValueError(
            f"bank_of needs homogeneous sketches; got shapes {shapes}, "
            f"dtypes {dtypes}"
        )
    return SketchBank(
        counts=jnp.stack([s.counts for s in sketches]),
        n=jnp.stack([jnp.asarray(s.n, jnp.int32) for s in sketches]),
    )


def bank_query(
    bank: SketchBank, codes: Array, sketch_idx: Array, paired: bool = False
) -> Array:
    """RACE estimate with a per-point sketch index (the banked :func:`query`).

    Args:
      bank: the sketch bank.
      codes: ``(..., R)`` query codes (shared hash family).
      sketch_idx: ``(...,)`` int32 — which sketch each point reads.
      paired: True for PRP sketches (divide by that sketch's ``2n``).

    Returns:
      ``(...,)`` float32 estimates; point ``i`` is exactly
      ``query(bank.select(sketch_idx[i]), codes[i], paired)``.
    """
    gathered = bank.counts[
        sketch_idx[..., None], _row_ids(codes), codes
    ].astype(jnp.float32)
    mean_count = jnp.mean(gathered, axis=-1)
    denom = jnp.maximum(bank.n[sketch_idx].astype(jnp.float32), 1.0)
    if paired:
        denom = 2.0 * denom
    return mean_count / denom


def query_theta_banked(
    bank: SketchBank,
    params: lsh.LSHParams,
    theta_tilde: Array,
    sketch_idx: Array,
    paired: bool = True,
) -> Array:
    """Banked surrogate-risk estimate: one hashed gather serves S tenants."""
    return bank_query(bank, lsh.query_codes(params, theta_tilde), sketch_idx,
                      paired=paired)


# ---------------------------------------------------------------------------
# Streaming convenience: fold a stream of batches into the sketch with scan.
# ---------------------------------------------------------------------------


def resolve_engine(engine: str) -> str:
    """Resolve an insert/query engine name to ``scan`` or ``kernel``.

    Single owner of the ``auto`` rule (kernel on TPU, scan elsewhere) so
    insert and query sides can never disagree on what ``auto`` means.
    """
    if engine not in ("auto", "scan", "kernel"):
        raise ValueError(f"unknown engine {engine!r}; use auto | scan | kernel")
    if engine == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "scan"
    return engine


def sketch_dataset(
    params: lsh.LSHParams,
    z: Array,
    rows: Optional[int] = None,
    buckets: Optional[int] = None,
    batch: int = 1024,
    paired: bool = True,
    dtype: jnp.dtype = jnp.int32,
    vary_axes: tuple = (),
    engine: str = "auto",
) -> Sketch:
    """One-pass sketch of a full (pre-scaled) dataset ``z: (n, dim)``.

    Pads ``n`` up to a batch multiple and scans, emulating the streaming
    setting; padding rows are hashed but masked out of the counts.

    ``vary_axes``: mesh axis names to mark the scan carry as varying over —
    required when called inside ``shard_map`` (JAX vma tracking).

    ``engine`` selects the insert path: ``"scan"`` is the pure-jnp
    hash + scatter-add scan below; ``"kernel"`` streams batches through the
    fused Pallas histogram engine (``repro.kernels.ops.sketch_stream``,
    DESIGN.md §3.4); ``"auto"`` picks the kernel on TPU and the scan
    elsewhere. Engines agree up to floating-point sign ties in the paired
    projection (a tied point moves to a sibling bucket in the same row —
    row masses exact; see DESIGN.md §3.2). ``vary_axes`` (shard_map callers)
    always uses the scan path.
    """
    rows = rows if rows is not None else params.rows
    buckets = buckets if buckets is not None else params.buckets
    resolved = resolve_engine(engine)
    if resolved == "kernel" and not vary_axes:
        if rows != params.rows or buckets != params.buckets:
            if engine == "kernel":  # explicit request we cannot honor
                raise ValueError(
                    "engine='kernel' derives rows/buckets from params; "
                    f"got overrides rows={rows}, buckets={buckets}"
                )
        else:
            from repro.kernels import ops as kernel_ops  # deferred: ops imports us

            # Narrow dtypes ride the kernel's native tile path: int32 VMEM
            # scratch, one epilogue saturate — the device never holds an
            # int32 copy of the counters (DESIGN.md §12).
            sk = kernel_ops.sketch_stream(params, z, batch=batch,
                                          paired=paired,
                                          dtype=jnp.dtype(dtype))
            return Sketch(counts=sk.counts, n=sk.n)
    n, dim = z.shape
    n_pad = (-n) % batch
    zp = jnp.concatenate([z, jnp.zeros((n_pad, dim), z.dtype)], axis=0)
    mask = jnp.concatenate(
        [jnp.ones((n,), dtype), jnp.zeros((n_pad,), dtype)], axis=0
    )
    zp = zp.reshape(-1, batch, dim)
    maskp = mask.reshape(-1, batch)
    # Narrow output dtypes accumulate the scan carry in int32 (a stream can
    # exceed a 16-bit cell mid-scan) and saturate once at the end — counters
    # are monotone, so this equals per-batch saturation (DESIGN.md §6).
    carry_dtype = jnp.int32 if _is_narrow(dtype) else dtype
    counts, cnt = _scan_insert(params, zp, maskp, rows, buckets, paired,
                               carry_dtype, vary_axes=vary_axes)
    if _is_narrow(dtype):
        counts = saturating_cast(counts, dtype)
    return Sketch(counts=counts, n=cnt)


def _scan_insert(
    params: lsh.LSHParams,
    zp: Array,
    maskp: Array,
    rows: int,
    buckets: int,
    paired: bool,
    carry_dtype,
    vary_axes: tuple = (),
) -> Tuple[Array, Array]:
    """Scatter-add insert scan over pre-batched tiles — the shared program.

    ``zp: (steps, batch, dim)``, ``maskp: (steps, batch)``. Single owner of
    the per-batch step for BOTH the lone-stream build (:func:`sketch_dataset`)
    and the banked build (:func:`sketch_dataset_many` vmaps this function
    over the sketch axis), which is what makes per-tenant bank slices
    bit-identical to standalone builds: same primitives, same batch
    boundaries, and masked padding rows scatter integer zeros.

    Returns ``(counts (rows, buckets) carry_dtype, n () int32)``.
    """
    row_offset = (jnp.arange(rows, dtype=jnp.int32) * buckets)[None, :]

    def flat_add(counts: Array, codes: Array, mb: Array) -> Array:
        # flat 1-D scatter: ~17% faster than 2-D fancy indexing on CPU
        # (EXPERIMENTS.md §Perf hillclimb A) and identical counts.
        flat = counts.reshape(-1)
        idx = (row_offset + codes).reshape(-1)
        upd = jnp.broadcast_to(mb[:, None], codes.shape).reshape(-1)
        return flat.at[idx].add(upd).reshape(rows, buckets)

    def step(carry, xs):
        counts, cnt = carry
        zb, mb = xs
        mb = mb.astype(counts.dtype)
        if paired:
            cpos, cneg = lsh.prp_codes(params, zb)
            counts = flat_add(counts, cpos, mb)
            counts = flat_add(counts, cneg, mb)
        else:
            codes = lsh.srp_codes(params, zb)
            counts = flat_add(counts, codes, mb)
        return (counts, cnt + jnp.sum(mb).astype(jnp.int32)), None

    init = (jnp.zeros((rows, buckets), carry_dtype),
            jnp.zeros((), dtype=jnp.int32))
    if vary_axes:
        init = jax.tree.map(
            lambda t: jax.lax.pcast(t, tuple(vary_axes), to="varying"), init)
    (counts, cnt), _ = jax.lax.scan(step, init, (zp, maskp))
    return counts, cnt


def stack_ragged(zs) -> Tuple[Array, Array]:
    """Stack ragged per-tenant streams into a mask-padded sketch-major block.

    ``zs`` is a ``(S, n, dim)`` stack (returned as-is with an all-ones mask)
    or a sequence of ``(n_s, dim)`` arrays with possibly unequal ``n_s``;
    shorter streams are zero-padded to the longest and masked out. The
    ``(stacked (S, n_max, dim), mask (S, n_max))`` pair is the input contract
    of every fused banked insert (:func:`sketch_dataset_many`,
    ``kernels.ops.sketch_insert_banked``, the gateway's ingest tick).
    """
    if hasattr(zs, "ndim"):
        if zs.ndim != 3:
            raise ValueError(f"stacked streams must be (S, n, dim); got "
                             f"shape {zs.shape}")
        zs = jnp.asarray(zs)
        return zs, jnp.ones(zs.shape[:2], jnp.float32)
    arrs = [jnp.asarray(z) for z in zs]
    if not arrs:
        raise ValueError("need at least one tenant stream")
    dims = {a.shape[-1] for a in arrs}
    if len(dims) != 1 or any(a.ndim != 2 for a in arrs):
        raise ValueError(f"tenant streams must share one (n_s, dim) shape "
                         f"family; got dims {dims}")
    n_max = max(a.shape[0] for a in arrs)
    stacked = jnp.stack(
        [jnp.pad(a, ((0, n_max - a.shape[0]), (0, 0))) for a in arrs]
    )
    mask = jnp.stack([
        (jnp.arange(n_max) < a.shape[0]).astype(jnp.float32) for a in arrs
    ])
    return stacked, mask


@functools.partial(
    jax.jit, static_argnames=("rows", "buckets", "batch", "paired")
)
def _sketch_banked_scan(
    params: lsh.LSHParams,
    zs: Array,
    mask: Array,
    rows: int,
    buckets: int,
    batch: int,
    paired: bool,
) -> Tuple[Array, Array]:
    """Vmapped :func:`_scan_insert` over the sketch axis (int32 carry)."""
    s, n, dim = zs.shape
    n_pad = (-n) % batch
    zp = jnp.concatenate(
        [zs, jnp.zeros((s, n_pad, dim), zs.dtype)], axis=1
    ).reshape(s, -1, batch, dim)
    mp = jnp.concatenate(
        [mask, jnp.zeros((s, n_pad), mask.dtype)], axis=1
    ).reshape(s, -1, batch)
    return jax.vmap(
        lambda zb, mb: _scan_insert(params, zb, mb, rows, buckets, paired,
                                    jnp.int32)
    )(zp, mp)


def sketch_dataset_many(
    params: lsh.LSHParams,
    zs,
    rows: Optional[int] = None,
    buckets: Optional[int] = None,
    batch: int = 1024,
    paired: bool = True,
    dtype: jnp.dtype = jnp.int32,
    engine: str = "auto",
) -> SketchBank:
    """Sketch S datasets under ONE shared hash family into a bank — fused.

    ``zs`` is a ``(S, n, dim)`` stack or any sequence of ``(n_s, dim)``
    arrays (per-tenant streams may differ in length; :func:`stack_ragged`
    mask-pads them to a common block). There is no host loop over tenants:
    the ``scan`` engine vmaps the shared scatter-add scan
    (:func:`_scan_insert`) over the sketch axis, and the ``kernel`` engine
    streams the stack through the grid-over-S fused histogram
    (``kernels.ops.sketch_insert_banked``) — one program either way.

    Slice ``s`` of the returned bank is bit-identical to the standalone
    :func:`sketch_dataset` build of stream ``s`` under the same engine: the
    per-batch step is the same function, batch boundaries align (both pad to
    a ``batch`` multiple), mask-padding rows scatter integer zeros, and
    narrow dtypes follow the same int32-carry + one final saturation
    discipline (DESIGN.md §6) — so the bank stays a pure re-layout, not a
    new estimator.
    """
    rows = rows if rows is not None else params.rows
    buckets = buckets if buckets is not None else params.buckets
    zs_stacked, mask = stack_ragged(zs)
    resolved = resolve_engine(engine)
    if resolved == "kernel":
        if rows != params.rows or buckets != params.buckets:
            if engine == "kernel":  # explicit request we cannot honor
                raise ValueError(
                    "engine='kernel' derives rows/buckets from params; "
                    f"got overrides rows={rows}, buckets={buckets}"
                )
        else:
            from repro.kernels import ops as kernel_ops  # deferred: ops imports us

            bank = kernel_ops.sketch_insert_banked(
                params, zs_stacked, mask, batch=batch, paired=paired,
                dtype=jnp.dtype(dtype)
            )
            return SketchBank(counts=bank.counts, n=bank.n)
    counts, cnt = _sketch_banked_scan(params, zs_stacked, mask, rows=rows,
                                      buckets=buckets, batch=batch,
                                      paired=paired)
    if _is_narrow(dtype):
        counts = saturating_cast(counts, dtype)
    elif counts.dtype != jnp.dtype(dtype):
        counts = counts.astype(dtype)
    return SketchBank(counts=counts, n=cnt)
