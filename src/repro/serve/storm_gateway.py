"""STORM serving gateway: one fused banked call per tick (DESIGN.md §10–11).

The sketch — not the data — is what lives at the edge and gets queried
online, so the serving unit is a :class:`~repro.core.sketch.SketchBank`: S
tenants' counter tables behind one endpoint. The gateway micro-batches two
request classes over fixed engine ticks:

* **ingest** — ``(tenant, z-rows)`` appended to that tenant's counters. All
  pending rows coalesce into ONE fused banked antithetic insert per tick
  (``ops.paired_hash_histogram_banked`` over a mask-padded ``(S, I, dim)``
  stack — the grid-over-S kernel on TPU, the vmapped oracle elsewhere).
* **query** — surrogate-loss evaluation of a theta batch (a client fleet's
  candidates) against that tenant's sketch. All pending points coalesce into
  ONE banked ``ops.query_theta_with_weights(bank, ..., points_per_table)``
  call.
* **fit** — train a tenant cohort end-to-end from its served counters: one
  ``erm.fit_many`` over the cohort's live sub-bank, for any registered
  surrogate whose insert flavor matches the gateway's. Fits drain between
  ticks (at ``tick_finish``, post-ingest) and compile their own loss
  closures, so the three-tick-program jit-stability invariant is untouched.

Both halves run inside jitted tick programs over **jit-stable padded
shapes**: per-tenant slot capacities (``ingest_slots`` rows, ``query_slots``
points) fix every buffer shape, masks mark real traffic, and overflow simply
waits for the next tick. A tick dispatches one of exactly three fixed
programs — ingest+query, ingest-only, query-only, matching which halves
carry traffic — so the engine never recompiles under any request mix
(asserted via the jit caches in tests), and a read-heavy tick does not pay
for an empty insert. Within a mixed tick, ingest applies first and queries
read the post-ingest counters (read-your-writes). On the meshless path each
tick ships ONE fused host buffer to the device (four tiny transfers cost
more than the fused query itself at serving shapes).

**Double-buffered serving (DESIGN.md §11).** A tick is two host-visible
stages: :meth:`StormGateway.tick_start` packs pending traffic and dispatches
the fused programs WITHOUT blocking (JAX async dispatch — the returned
counter/estimate arrays are futures), and :meth:`StormGateway.tick_finish`
performs the only D2H readback (the loss estimates) and reports completions.
``tick()`` is exactly ``tick_finish(tick_start())``, so the synchronous loop
is the depth-1 special case and bit-identity of the pipelined loop is by
construction: packing (the only queue mutation) happens at start time in
dispatch order, the device chains tick t+1's programs on tick t's output
arrays, and readback order equals dispatch order. A driver that keeps two
ticks in flight (``run_until_idle(pipelined=True)``, or the wire server's
engine thread) overlaps tick t+1's host packing with tick t's device
execution and pays ``jax.block_until_ready``-equivalent waits only at
result-completion time, never between ticks.

Admission control: optional per-tenant ``max_pending_rows`` /
``max_pending_points`` caps bound the queues — a submit that would exceed a
tenant's cap raises :class:`Backpressure` (the wire front-end turns this
into an explicit retryable response) instead of growing an unbounded deque.
Slot capacity is per-tenant, so one tenant's flood can neither starve
another tenant's tick slots nor, with caps set, its queue memory.

The tenant-major slot layout is deliberately the member-major contract of
banked fleets (``fleet.member_point_idx`` with ``member_map = arange(S)``),
so a mesh splits tenants across devices exactly like
``distributed.fleet_fit_banked`` splits a training bank
(``sharding.specs.gateway_specs``): each device owns its tenants' tables and
exactly those tenants' tick slots — zero per-tick communication.

Tracing (DESIGN.md §11.5): the tick's stages record ``storm.gw.*`` spans
(``jax.profiler.TraceAnnotation``) at tick granularity, and collections of
the oldest generation a ``storm.gc`` span; they cost an enter and an exit
each, and record nothing unless a profiler session is active.

Correctness contract (pinned in ``tests/test_serve_gateway.py`` and
``tests/test_serve_async.py``): a tenant's counters after any interleaving
of gateway ticks are bit-identical to the standalone ``sketch_dataset``
build of its stream, a tenant's query results are bit-identical to
standalone ``ops.query_theta_with_weights`` calls against its lone sketch,
and the pipelined loop is bit-identical to the synchronous loop — reports,
counters, and result ordering included.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import time
from collections import defaultdict, deque
from typing import Callable, Deque, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (dfo, erm, fleet, losses, lsh,
                        privacy as privacy_lib, sketch as sketch_lib)
from repro.kernels import ops

Array = jax.Array
span = jax.profiler.TraceAnnotation


class _GcSpan:
    """``gc.callbacks`` hook: a ``storm.gc`` span over each collection of
    the oldest generation, the one long enough to stall a tick. Younger
    collections return at once."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._open = span("storm.gc")
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


_GC_SPAN = _GcSpan()


def _wait_ms(queue) -> float:
    """Milliseconds the head of a FIFO queue, its oldest request, has
    waited since its submit."""
    return (time.perf_counter() - queue[0].enqueued) * 1e3


class Backpressure(RuntimeError):
    """A submit would exceed a tenant's bounded-queue capacity.

    Explicit backpressure instead of unbounded queue growth: the caller
    (or the wire front-end, which relays this as a retryable error frame)
    should drain completions and resubmit.
    """

    def __init__(self, tenant: int, kind: str, pending: int, requested: int,
                 limit: int):
        super().__init__(
            f"tenant {tenant} {kind} queue full: {pending} pending + "
            f"{requested} requested > cap {limit}"
        )
        self.tenant = tenant
        self.kind = kind  # "ingest" | "query"
        self.pending = pending
        self.requested = requested
        self.limit = limit


class TickBudgetExceeded(RuntimeError):
    """``run_until_idle`` exhausted its tick budget with requests pending.

    Results that DID complete within the budget are attached as
    ``completed`` (and the number of still-queued requests as ``pending``)
    so a caller can salvage partial progress instead of losing every
    already-served answer.
    """

    def __init__(self, pending: int, completed: List["QueryResult"]):
        super().__init__(f"{pending} requests still pending after the tick "
                         f"budget ({len(completed)} results completed)")
        self.pending = pending
        self.completed = completed


@dataclasses.dataclass
class IngestRequest:
    """Append ``z`` rows to a tenant's counters. For a ``paired`` gateway
    these are pre-scaled sketch-space points (``params.dim - 2`` wide; the
    PRP insert augments internally); for a single-sided gateway they are
    pre-augmented points (``params.dim`` wide — the classification
    contract, ``lsh.augment_data`` applied by the client). Rows beyond the
    tick capacity spill to later ticks."""

    rid: int
    tenant: int
    z: np.ndarray


@dataclasses.dataclass
class QueryRequest:
    """Evaluate the sketch loss at ``thetas`` (``(q, dim)`` iterates, e.g. a
    client fleet's candidates) against a tenant's sketch."""

    rid: int
    tenant: int
    thetas: np.ndarray


@dataclasses.dataclass
class FitRequest:
    """Train a tenant cohort from its SERVED counters (the third request
    class, DESIGN.md §13): one ``erm.fit_many`` over the named tenants'
    live sketches, dispatched between ticks.

    ``surrogate`` names a registered :mod:`repro.core.losses` spec whose
    insert flavor must match the gateway's (``spec.paired == gw.paired``) —
    the counters were built by the gateway's insert path, so only
    same-flavor surrogates read them correctly. The fit compiles its own
    loss closures (separate jit caches), so the three-tick-program
    ``trace_count`` invariant is untouched.
    """

    rid: int
    tenants: Sequence[int]          # the cohort, in result-row order
    surrogate: str = "prp_regression"
    seed: int = 0
    restarts: int = 1
    l2: float = 0.0
    steps: int = 100                # DFO steps (serving fits favor short runs)
    num_queries: int = 8
    sigma: float = 0.5
    learning_rate: float = 1.0
    decay: float = 0.995
    refine_steps: Optional[int] = None  # None -> the surrogate's default


@dataclasses.dataclass
class FitResult:
    """Iterate-space cohort fit: row ``i`` is ``tenants[i]``'s model.

    ``status`` is the privacy verdict under a finite
    :class:`~repro.core.privacy.ReleasePolicy`: ``"ok"`` (fresh releases),
    ``"stale"`` (at least one cohort member trained from its last cached
    release), or ``"refused"`` (an exhausted member with no stale release —
    ``theta``/``fleet_losses`` are zero placeholders).
    """

    rid: int
    tenants: List[int]
    theta: np.ndarray         # (S, dim) float32
    fleet_losses: np.ndarray  # (S, F) final sketch-loss per restart member
    status: str = "ok"


@dataclasses.dataclass
class QueryResult:
    """``status``: ``"ok"``, ``"stale"`` (served from the tenant's last
    cached release after budget exhaustion), or ``"refused"`` (exhausted,
    ``losses`` are zeros — the wire relays a terminal ``budget_exceeded``
    frame instead of a result)."""

    rid: int
    tenant: int
    losses: np.ndarray  # (q,) float32, row i for thetas[i]
    status: str = "ok"


@dataclasses.dataclass
class IngestResult:
    """An ingest request's final row reached the counters this tick."""

    rid: int
    tenant: int
    rows: int


@dataclasses.dataclass
class TickReport:
    """What one engine tick did (completed requests only — a split request
    reports once, on the tick that finishes it)."""

    tick: int
    results: List[QueryResult]
    rows_ingested: int
    points_served: int
    ingest_done: List[IngestResult] = dataclasses.field(default_factory=list)
    fits: List[FitResult] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _PendingIngest:
    req: IngestRequest
    cursor: int = 0
    enqueued: float = dataclasses.field(default_factory=time.perf_counter)


@dataclasses.dataclass
class _PendingQuery:
    req: QueryRequest
    cursor: int = 0
    out: Optional[np.ndarray] = None
    status: str = "ok"
    enqueued: float = dataclasses.field(default_factory=time.perf_counter)


@dataclasses.dataclass
class InflightTick:
    """One dispatched-but-unread tick (DESIGN.md §11 stage contract).

    Everything queue-related was resolved at :meth:`StormGateway.tick_start`
    time; ``est`` is the only device future a finish must wait on, and
    ``placements``/``completes``/``ingest_done`` are the host-side
    bookkeeping that turns the readback into :class:`TickReport` entries.
    :meth:`StormGateway.tick_finish` consumes it: the bookkeeping is
    released once the report is built.
    """

    tick: int
    est: Optional[Array]  # device future of the fused query, or None
    placements: list  # (pending, req_offset, tenant, slot_offset, count)
    completes: List[_PendingQuery]  # finished packing; report at finish
    ingest_done: List[IngestResult]
    rows: int
    points: int


def run_fit_request(req: FitRequest, bank: sketch_lib.SketchBank,
                    params: lsh.LSHParams) -> FitResult:
    """Execute one cohort fit against an int32 sub-bank (row i = tenants[i]).

    Shared by the flat and tiered gateways: the request's knobs map onto
    ONE ``erm.fit_many`` call, so a gateway fit is bit-identical to the
    offline spine fit over the same counters and seed.
    """
    cfg = dfo.DFOConfig(
        steps=req.steps, num_queries=req.num_queries, sigma=req.sigma,
        learning_rate=req.learning_rate, decay=req.decay,
    )
    res = erm.fit_many(
        req.surrogate, bank, params, jax.random.PRNGKey(req.seed),
        dfo_config=cfg, restarts=req.restarts, l2=req.l2,
        refine_steps=req.refine_steps,
    )
    return FitResult(rid=req.rid, tenants=list(req.tenants),
                     theta=np.asarray(res.theta),
                     fleet_losses=np.asarray(res.fleet_losses))


def _jit_cache_size(f) -> Optional[int]:
    """Best-effort read of a jitted function's trace-cache size.

    ``f._cache_size()`` is private jit API and has moved/broken across JAX
    releases; returning ``None`` (instead of raising, or silently returning
    0) routes :attr:`StormGateway.trace_count` to the gateway's own
    trace-event counter so the jit-stability invariant stays ENFORCED
    rather than vacuously skipped.
    """
    try:
        size = f._cache_size()
    except Exception:
        return None
    return size if isinstance(size, int) else None


class StormGateway:
    """Fixed-tick micro-batching gateway over a :class:`SketchBank`."""

    def __init__(
        self,
        params: lsh.LSHParams,
        tenants: int,
        *,
        paired: bool = True,
        query_slots: int = 32,
        ingest_slots: int = 128,
        count_dtype=jnp.int32,
        mode: str = "auto",
        bank: Optional[sketch_lib.SketchBank] = None,
        mesh=None,
        axis: str = "bank",
        max_pending_rows: Optional[int] = None,
        max_pending_points: Optional[int] = None,
        privacy: Optional[privacy_lib.ReleasePolicy] = None,
        privacy_seed: int = 0,
        private_view: Optional[privacy_lib.PrivateBankView] = None,
        privacy_key_of: Optional[Callable[[int], int]] = None,
    ):
        """Args:
          params: the ONE hash family shared by every tenant's sketch.
          tenants: bank size S (fixed for the gateway's lifetime — the
            tick's padded shapes depend on it).
          paired: PRP sketches (regression/probes) vs single-sided
            (classification margin) — sets both the insert kernel and the
            estimator denominator.
          query_slots: per-tenant theta capacity Q per tick.
          ingest_slots: per-tenant row capacity I per tick.
          count_dtype: counter dtype; narrow dtypes widen per tick and
            saturate on the way back (DESIGN.md §6).
          mode: kernel dispatch for both halves (``auto | kernel |
            interpret | ref``).
          bank: optional warm-start counters (shape ``(S, R, B)``); its
            dtype overrides ``count_dtype``.
          mesh / axis: optional device mesh splitting tenants over ``axis``
            (``sharding.specs.gateway_specs``); ``None`` runs the identical
            program unsharded.
          max_pending_rows: per-tenant cap on queued ingest rows; a submit
            that would exceed it raises :class:`Backpressure`. ``None``
            leaves the queue unbounded.
          max_pending_points: per-tenant cap on queued query points;
            ``None`` = unbounded.
          privacy: optional :class:`~repro.core.privacy.ReleasePolicy`.
            ``None`` or a noiseless policy (``epsilon_release = inf``)
            leaves the gateway EXACTLY as before — the private machinery
            (4th tick program, lane buffer, ledger) is not even built, so
            eps=inf is bit-identical by construction. A finite policy makes
            every query tick a privatize-on-read: ONE noisy release per
            (tenant, tick) covers all coalesced queries, charged to the
            per-tenant ledger; exhausted tenants refuse or serve their
            last cached release per ``policy.on_exhaust``.
          privacy_seed: PRNG seed of the release noise stream.
          private_view: inject a shared
            :class:`~repro.core.privacy.PrivateBankView` (the tiered
            gateway shares ONE global view with its inner gateway).
          privacy_key_of: maps a bank slot to its ledger key (identity by
            default; the tiered gateway maps slot -> GLOBAL tenant so
            budgets follow tenants across promote/demote).
        """
        if tenants < 1:
            raise ValueError(f"need at least one tenant; got {tenants}")
        self.params = params
        self.w = ops.from_lsh_params(params)
        self.dim = params.dim - 2  # query iterate dim (theta_tilde rows)
        # Paired ingest takes raw sketch-space rows (augmented internally);
        # single-sided ingest takes pre-augmented rows at params.dim (the
        # classification contract — clients apply lsh.augment_data).
        self.ingest_dim = params.dim - 2 if paired else params.dim
        self.tenants = tenants
        self.paired = paired
        self.query_slots = query_slots
        self.ingest_slots = ingest_slots
        self.mode = mode
        self.mesh = mesh
        self.axis = axis
        self.max_pending_rows = max_pending_rows
        self.max_pending_points = max_pending_points
        if bank is None:
            bank = sketch_lib.SketchBank(
                counts=jnp.zeros((tenants, params.rows, params.buckets),
                                 jnp.dtype(count_dtype)),
                n=jnp.zeros((tenants,), jnp.int32),
            )
        if bank.counts.shape[0] != tenants:
            raise ValueError(
                f"bank holds {bank.counts.shape[0]} sketches for "
                f"{tenants} tenants"
            )
        self.count_dtype = bank.counts.dtype
        self._counts = bank.counts
        self._n = bank.n
        self._ingest_q: Deque[_PendingIngest] = deque()
        self._query_q: Deque[_PendingQuery] = deque()
        self._fit_q: Deque[FitRequest] = deque()
        self._pending_rows = [0] * tenants
        self._pending_points = [0] * tenants
        self.ticks = 0
        self.rows_ingested = 0
        self.points_served = 0
        self.fits_run = 0
        self.queries_refused = 0
        self.fits_refused = 0
        self._trace_events = 0  # fallback trace counter (see trace_count)
        # Program name -> the banked query schedule it compiled with
        # (``ops.query_path``), recorded when the program is traced.
        self.query_paths: Dict[str, str] = {}

        # Privacy layer (DESIGN.md §15). eps=inf / no policy builds NOTHING:
        # the non-private tick programs below are the whole gateway, so the
        # unlimited-budget path is bit-identical to the pre-privacy gateway
        # by construction (there is no zero-noise float path to diverge).
        self.privacy = privacy
        self._private = privacy is not None and not privacy.noiseless
        self._privacy_key_of = privacy_key_of or (lambda slot: slot)
        self.private_view: Optional[privacy_lib.PrivateBankView] = None
        self._tick_query_private = None
        if self._private:
            if mesh is not None:
                raise NotImplementedError(
                    "finite-epsilon privacy is meshless-only for now; "
                    "eps=inf (ReleasePolicy.unlimited() or privacy=None) "
                    "runs on a mesh unchanged")
            self.private_view = (private_view if private_view is not None
                                 else privacy_lib.PrivateBankView(
                                     privacy, seed=privacy_seed))
            # Device-side stale lanes: slot i carries tenant i's last
            # released table so an exhausted tenant can be served its
            # cached release without any host round-trip.
            self._release_buf = jnp.zeros(
                (tenants, params.rows, params.buckets), jnp.float32)
            # Host-tracked counter versions (cumulative packed rows == the
            # device n, exactly — the host packs every row), keyed by the
            # ledger key so versions follow tenants across slot reuse.
            self._rows_of: Dict[int, int] = defaultdict(int)
            init_n = np.asarray(bank.n)
            if init_n.any():  # warm-start bank: seed the version tracker
                for slot in range(tenants):
                    if init_n[slot]:
                        self._rows_of[self._privacy_key_of(slot)] += \
                            int(init_n[slot])

        self._tick_full, self._tick_ingest, self._tick_query = \
            self._build_ticks()
        if self._private:
            self._tick_query_private = self._build_private_tick()
        if _GC_SPAN not in gc.callbacks:
            gc.callbacks.append(_GC_SPAN)

    # -- request plumbing ---------------------------------------------------

    def submit(self, req: Union[IngestRequest, QueryRequest, FitRequest]
               ) -> None:
        if isinstance(req, FitRequest):
            cohort = [int(t) for t in req.tenants]
            if not cohort:
                raise ValueError("fit cohort is empty")
            for t in cohort:
                if not 0 <= t < self.tenants:
                    raise ValueError(f"fit tenant {t} out of range "
                                     f"[0, {self.tenants})")
            spec = losses.get_surrogate(req.surrogate)
            if spec.paired != self.paired:
                flavor = ("paired (PRP)", "single-sided")
                raise ValueError(
                    f"surrogate '{spec.name}' expects "
                    f"{flavor[0] if spec.paired else flavor[1]} counters but "
                    f"this gateway ingests "
                    f"{flavor[0] if self.paired else flavor[1]}"
                )
            self._fit_q.append(dataclasses.replace(req, tenants=cohort))
            return
        if not 0 <= req.tenant < self.tenants:
            raise ValueError(f"tenant {req.tenant} out of range "
                             f"[0, {self.tenants})")
        if isinstance(req, IngestRequest):
            z = np.asarray(req.z, np.float32)
            if z.ndim != 2 or z.shape[1] != self.ingest_dim:
                raise ValueError(
                    f"ingest rows must be (rows, {self.ingest_dim}); got "
                    f"{z.shape}"
                )
            if self.max_pending_rows is not None and (
                    self._pending_rows[req.tenant] + z.shape[0]
                    > self.max_pending_rows):
                raise Backpressure(req.tenant, "ingest",
                                   self._pending_rows[req.tenant],
                                   z.shape[0], self.max_pending_rows)
            self._pending_rows[req.tenant] += z.shape[0]
            self._ingest_q.append(_PendingIngest(dataclasses.replace(req, z=z)))
        elif isinstance(req, QueryRequest):
            th = np.asarray(req.thetas, np.float32)
            if th.ndim != 2 or th.shape[1] != self.dim:
                raise ValueError(f"query thetas must be (q, {self.dim}); "
                                 f"got {th.shape}")
            if self.max_pending_points is not None and (
                    self._pending_points[req.tenant] + th.shape[0]
                    > self.max_pending_points):
                raise Backpressure(req.tenant, "query",
                                   self._pending_points[req.tenant],
                                   th.shape[0], self.max_pending_points)
            self._pending_points[req.tenant] += th.shape[0]
            self._query_q.append(_PendingQuery(
                dataclasses.replace(req, thetas=th),
                out=np.zeros((th.shape[0],), np.float32),
            ))
        else:
            raise TypeError(f"unknown request type {type(req).__name__}")

    def submit_many(self, reqs: Sequence[Union[IngestRequest, QueryRequest,
                                               FitRequest]]) -> None:
        for r in reqs:
            self.submit(r)

    @property
    def pending(self) -> int:
        return len(self._ingest_q) + len(self._query_q) + len(self._fit_q)

    def queue_stats(self) -> dict:
        """Host-side gateway state for monitoring / the wire stats reply.

        ``pending_depth[t]`` is the number of queued REQUESTS for tenant
        ``t`` (ingest + query, split requests still counting once) —
        the row/point tallies alone can't distinguish one giant request
        from a pile of small ones, which is exactly what Backpressure
        tuning needs to see.
        """
        depth = [0] * self.tenants
        for st in self._ingest_q:
            depth[st.req.tenant] += 1
        for st in self._query_q:
            depth[st.req.tenant] += 1
        stats = {
            "tenants": self.tenants,
            "ticks": self.ticks,
            "pending_requests": self.pending,
            "pending_depth": depth,
            "pending_rows": list(self._pending_rows),
            "pending_points": list(self._pending_points),
            "pending_fits": len(self._fit_q),
            "rows_ingested": self.rows_ingested,
            "points_served": self.points_served,
            "fits_run": self.fits_run,
            "trace_count": self.trace_count,
        }
        if self._private:
            stats["privacy"] = dict(self.private_view.summary(),
                                    queries_refused=self.queries_refused,
                                    fits_refused=self.fits_refused)
        return stats

    @property
    def bank(self) -> sketch_lib.SketchBank:
        """The live counter bank (device arrays; post-last-tick state)."""
        return sketch_lib.SketchBank(counts=self._counts, n=self._n)

    def sketch_of(self, tenant: int) -> sketch_lib.Sketch:
        """Tenant ``tenant``'s sketch as a standalone view."""
        return self.bank.select(tenant)

    @property
    def trace_count(self) -> int:
        """Total traces across the fixed tick programs (jit-stability: this
        must stay <= 3 for any request mix over the gateway's lifetime —
        <= 4 with a finite privacy policy, which adds exactly ONE more
        fixed program, the masked noise-add private query).

        Prefers the jit caches (``_cache_size``, private API) and falls back
        to the gateway's own trace-event counter — each tick program bumps
        ``_trace_events`` when (and only when) its Python body is traced —
        so the invariant survives JAX versions that rename the private
        accessor instead of silently reporting zero.
        """
        progs = [self._tick_full, self._tick_ingest, self._tick_query]
        if self._tick_query_private is not None:
            progs.append(self._tick_query_private)
        sizes = [_jit_cache_size(f) for f in progs]
        if any(s is None for s in sizes):
            return self._trace_events
        return sum(sizes)

    # -- the fused tick -----------------------------------------------------

    def _counting(self, fn, query_path: Optional[str] = None):
        """Bump the fallback trace counter, under a ``storm.gw.trace``
        span, when ``fn``'s body is traced; a program with a query half
        records its ``query_path`` in :attr:`query_paths` and on the span.

        Both are Python side effects, so under ``jax.jit`` they run once per
        trace (cache miss), never per call — exactly the event
        ``trace_count`` wants when ``_cache_size`` is unavailable, and one
        span per compile in a profile (the span covers the tracing, not
        XLA's compile after it). The wrapper keeps ``fn``'s name, which
        ``jax.jit`` gives the program.
        """
        stats = {} if query_path is None else {"query_path": query_path}

        @functools.wraps(fn)
        def wrapped(*args):
            self._trace_events += 1
            if query_path is not None:
                self.query_paths[fn.__name__] = query_path
            with span("storm.gw.trace", program=fn.__name__, **stats):
                return fn(*args)
        return wrapped

    def _build_ticks(self):
        """Build the three fixed tick programs (full / ingest / query).

        Each is its own jitted program over the same padded shapes — the
        tick picks one by which halves carry traffic, so a read-heavy tick
        never executes an all-masked insert (on these shapes the empty
        paired histogram costs several times the fused query itself).
        """
        w = self.w
        paired = self.paired
        mode = self.mode
        dtype = self.count_dtype
        s, dim, in_dim = self.tenants, self.dim, self.ingest_dim
        i_cap, q_cap = self.ingest_slots, self.query_slots
        path = ops.query_path(mode, self.params.dim, points_per_table=q_cap)

        def ingest_half(counts, n, zbuf, zmask):
            # ONE fused banked insert over the (S, I, dim) stack. Narrow
            # banks get narrow tiles straight from the kernel (int32 stays
            # in VMEM scratch, one epilogue saturate — DESIGN.md §12) and
            # the saturating carry add; since increments are non-negative,
            # clamp(counts + clamp(tile)) == clamp(counts + tile), so this
            # is bit-identical to the widen-the-whole-bank path it replaces.
            if paired:
                tile = ops.paired_hash_histogram_banked(zbuf, w, zmask,
                                                        mode=mode,
                                                        out_dtype=dtype)
            else:
                tile = ops.hash_histogram_banked(zbuf, w, zmask, mode=mode,
                                                 out_dtype=dtype)
            new_counts = sketch_lib.saturating_add(counts, tile)
            return new_counts, n + jnp.sum(zmask, axis=1).astype(jnp.int32)

        def query_half(counts, n, qbuf, qmask):
            # ONE banked call; tenant-major slots route row i to table
            # i // Q (the member-major contract, member_map = arange(S)),
            # over the local tenants on a mesh too.
            est = ops.query_theta_with_weights(
                sketch_lib.SketchBank(counts=counts, n=n),
                w, qbuf, paired=paired, mode=mode, points_per_table=q_cap,
            )
            return jnp.where(qmask > 0, est, 0.0)

        if self.mesh is None:
            # Meshless fast path: ONE fused host->device transfer per tick.
            # The flat buffer is [zbuf | zmask | qbuf | qmask] (the suffix a
            # variant doesn't need is simply not shipped); slicing happens
            # inside the compiled program.
            z_end, zm_end = s * i_cap * in_dim, s * i_cap * (in_dim + 1)

            def unpack_ingest(flat):
                return (flat[:z_end].reshape(s, i_cap, in_dim),
                        flat[z_end:zm_end].reshape(s, i_cap))

            def unpack_query(flat, off):
                q_end = off + s * q_cap * dim
                return (flat[off:q_end].reshape(s * q_cap, dim),
                        flat[q_end:q_end + s * q_cap])

            def tick_full(counts, n, flat):
                counts, n = ingest_half(counts, n, *unpack_ingest(flat))
                return counts, n, query_half(counts, n,
                                             *unpack_query(flat, zm_end))

            def tick_ingest(counts, n, flat):
                return ingest_half(counts, n, *unpack_ingest(flat))

            def tick_query(counts, n, flat):
                return query_half(counts, n, *unpack_query(flat, 0))

            return (jax.jit(self._counting(tick_full, path)),
                    jax.jit(self._counting(tick_ingest)),
                    jax.jit(self._counting(tick_query, path)))

        def tick_full(counts, n, zbuf, zmask, qbuf, qmask):
            counts, n = ingest_half(counts, n, zbuf, zmask)
            return counts, n, query_half(counts, n, qbuf, qmask)

        def tick_ingest(counts, n, zbuf, zmask):
            return ingest_half(counts, n, zbuf, zmask)

        def tick_query(counts, n, qbuf, qmask):
            return query_half(counts, n, qbuf, qmask)

        from repro.sharding import specs as sharding_specs

        bank_spec, _ = sharding_specs.gateway_specs(self.axis)
        sharding_specs.check_bank_divisible(self.tenants, self.mesh,
                                            self.axis)
        # Tick buffers get explicit tenant-axis shardings at dispatch time
        # (device_put before the call), so the h2d transfer of tick t+1 can
        # overlap tick t's execution instead of serializing inside the
        # sharded call (DESIGN.md §11 overlap invariant).
        self._in_shardings = sharding_specs.named(
            self.mesh, sharding_specs.gateway_input_specs(self.axis))

        def shard(fn, n_in, n_out, query_path=None):
            return jax.jit(self._counting(jax.shard_map(
                fn, mesh=self.mesh,
                in_specs=(bank_spec,) * n_in,
                out_specs=(bank_spec,) * n_out if n_out > 1 else bank_spec,
            ), query_path))

        return (shard(tick_full, 6, 3, path), shard(tick_ingest, 4, 2),
                shard(tick_query, 4, 1, path))

    def _build_private_tick(self):
        """The ONE extra fixed program of a finite privacy policy.

        A masked noise-add on the packed query buffer: per slot, either
        rebuild this tick's release (``f32(counts) + noise`` — fresh, or a
        bit-identical free rebuild inside an open window) or carry the
        slot's stale lane, then run the same fused banked query over the
        released f32 tables with the RELEASE-TIME denominators. The lanes
        are an output, so stale serving never needs a host round-trip. The
        flat buffer is ``[qbuf | qmask | noise | fresh]`` (same fused-H2D
        discipline as the other programs); ``n_used`` rides as a tiny int32
        side input to keep release counts exact beyond f32's 2^24.

        The banked query runs in ``mode="ref"`` — the released tables are
        f32 and the reference gather is the path specified for float
        counters (the int-tile Pallas kernels are not); the pure-jnp gather
        fuses fine inside this jitted program.
        """
        w = self.w
        paired = self.paired
        s, dim, q_cap = self.tenants, self.dim, self.query_slots
        r, b = self.params.rows, self.params.buckets

        def tick_query_private(counts, stale, flat, n_used):
            q_end = s * q_cap * dim
            qm_end = q_end + s * q_cap
            nz_end = qm_end + s * r * b
            qbuf = flat[:q_end].reshape(s * q_cap, dim)
            qmask = flat[q_end:qm_end]
            noise = flat[qm_end:nz_end].reshape(s, r, b)
            fresh = flat[nz_end:nz_end + s]
            released = jnp.where(fresh[:, None, None] > 0,
                                 counts.astype(jnp.float32) + noise, stale)
            idx = fleet.member_point_idx(
                jnp.arange(s, dtype=jnp.int32), qbuf.shape[0])
            est = ops.query_theta_with_weights(
                sketch_lib.SketchBank(counts=released, n=n_used),
                w, qbuf, paired=paired, mode="ref", sketch_idx=idx,
            )
            return released, jnp.where(qmask > 0, est, 0.0)

        return jax.jit(self._counting(tick_query_private, "ref"))

    def _pack_ingest(self):
        s, i_cap, dim = self.tenants, self.ingest_slots, self.ingest_dim
        zbuf = np.zeros((s, i_cap, dim), np.float32)
        zmask = np.zeros((s, i_cap), np.float32)
        fill = [0] * s
        taken = 0
        done: List[IngestResult] = []
        for st in self._ingest_q:
            t = st.req.tenant
            take = min(i_cap - fill[t], st.req.z.shape[0] - st.cursor)
            if take <= 0:
                continue
            zbuf[t, fill[t]:fill[t] + take] = st.req.z[
                st.cursor:st.cursor + take]
            zmask[t, fill[t]:fill[t] + take] = 1.0
            st.cursor += take
            fill[t] += take
            taken += take
            self._pending_rows[t] -= take
        remaining: Deque[_PendingIngest] = deque()
        for st in self._ingest_q:
            if st.cursor < st.req.z.shape[0]:
                remaining.append(st)
            else:
                done.append(IngestResult(st.req.rid, st.req.tenant,
                                         st.req.z.shape[0]))
        self._ingest_q = remaining
        return zbuf, zmask, taken, done

    def _pack_queries(self):
        s, q_cap, dim = self.tenants, self.query_slots, self.dim
        qbuf = np.zeros((s, q_cap, dim), np.float32)
        qmask = np.zeros((s, q_cap), np.float32)
        fill = [0] * s
        placements = []  # (pending, req_offset, tenant, slot_offset, count)
        for st in self._query_q:
            t = st.req.tenant
            take = min(q_cap - fill[t], st.req.thetas.shape[0] - st.cursor)
            if take <= 0:
                continue
            qbuf[t, fill[t]:fill[t] + take] = st.req.thetas[
                st.cursor:st.cursor + take]
            qmask[t, fill[t]:fill[t] + take] = 1.0
            placements.append((st, st.cursor, t, fill[t], take))
            st.cursor += take
            fill[t] += take
            self._pending_points[t] -= take
        # Fully-packed requests leave the queue NOW (dispatch order) and
        # report at finish time — including zero-row requests, which have
        # no rows to place but must still complete (possibly on a tick
        # whose query half is otherwise empty).
        completes: List[_PendingQuery] = []
        remaining: Deque[_PendingQuery] = deque()
        for st in self._query_q:
            if st.cursor == st.req.thetas.shape[0]:
                completes.append(st)
            else:
                remaining.append(st)
        self._query_q = remaining
        return qbuf, qmask, placements, completes

    # -- privatize-on-read planning (finite policy only) --------------------

    def _plan_private_reads(self) -> Dict[int, privacy_lib.ReadPlan]:
        """One ReadPlan per slot that will read counters this tick.

        Exactly the slots with >= 1 queued query point: per-tenant slot
        capacity guarantees each packs at least one point this tick, so
        each needs (at most) one release — the coalescing argument. Slots
        whose queue holds only zero-point requests read nothing and are
        not planned (an empty read must not spend budget). Runs AFTER
        ``_pack_ingest`` so plans see this tick's post-ingest versions
        (the program order: ingest applies first, read-your-writes).
        """
        shape = (self.params.rows, self.params.buckets)
        plans: Dict[int, privacy_lib.ReadPlan] = {}
        for slot in range(self.tenants):
            if self._pending_points[slot] <= 0:
                continue
            key = self._privacy_key_of(slot)
            plans[slot] = self.private_view.plan_read(
                key, self._rows_of[key], shape, paired=self.paired)
        return plans

    def _refuse_queries(self, refused_slots) -> List[_PendingQuery]:
        """Complete every pending query of the refused slots, typed.

        Refusal happens at plan time, BEFORE packing: refused requests
        never occupy tick slots, so other tenants in the same tick are
        untouched. Zero-point requests pass through (they read nothing —
        an exhausted tenant's empty query still completes ``"ok"``).
        """
        if not refused_slots:
            return []
        refused: List[_PendingQuery] = []
        remaining: Deque[_PendingQuery] = deque()
        for st in self._query_q:
            pts_left = st.req.thetas.shape[0] - st.cursor
            if st.req.tenant in refused_slots and pts_left > 0:
                st.status = "refused"
                st.out[st.cursor:] = 0.0
                self._pending_points[st.req.tenant] -= pts_left
                refused.append(st)
            else:
                remaining.append(st)
        self._query_q = remaining
        self.queries_refused += len(refused)
        return refused

    def _private_query_buffers(self, plans):
        """Per-slot (noise, fresh, n_used) arrays for the private program."""
        s = self.tenants
        noise = np.zeros((s, self.params.rows, self.params.buckets),
                         np.float32)
        fresh = np.zeros((s,), np.float32)
        n_used = np.zeros((s,), np.int32)
        for slot, plan in plans.items():
            n_used[slot] = plan.n
            if plan.status == "fresh":
                noise[slot] = plan.noise
                fresh[slot] = 1.0
        return noise, fresh, n_used

    def tick_start(self) -> InflightTick:
        """Pack pending traffic and dispatch the fused tick WITHOUT blocking.

        All queue mutation happens here, synchronously, in dispatch order;
        the returned :class:`InflightTick` carries the device future of the
        loss estimates (``est``) plus the host bookkeeping
        :meth:`tick_finish` needs. The counter/count arrays advance to the
        dispatched programs' outputs immediately — they are futures, and
        the next ``tick_start`` chains on them without a host sync, which
        is what lets a depth-2 driver pack tick t+1 while tick t runs.
        """
        self.ticks += 1
        if not self._ingest_q and not self._query_q:
            # Idle tick: nothing to pack, nothing to run.
            return InflightTick(tick=self.ticks, est=None, placements=[],
                                completes=[], ingest_done=[], rows=0,
                                points=0)
        rows, ingest_done = 0, []
        if self._ingest_q:
            with span("storm.gw.pack_ingest", requests=len(self._ingest_q),
                      oldest_wait_ms=_wait_ms(self._ingest_q)) as sp:
                zbuf, zmask, rows, ingest_done = self._pack_ingest()
                sp.set_metadata(rows=rows)
                if self._private and rows:
                    # Host version tracking: the packed rows ARE this
                    # tick's inserts, so versions advance exactly like the
                    # device n does.
                    per_slot = zmask.sum(axis=1)
                    for slot in np.nonzero(per_slot)[0]:
                        self._rows_of[self._privacy_key_of(int(slot))] += \
                            int(per_slot[slot])
        placements, completes, points = [], [], 0
        if self._query_q:
            with span("storm.gw.pack_queries", requests=len(self._query_q),
                      oldest_wait_ms=_wait_ms(self._query_q)) as sp:
                if self._private:
                    plans = self._plan_private_reads()
                    refused = self._refuse_queries(
                        {s for s, p in plans.items() if p.status == "refuse"})
                qbuf, qmask, placements, completes = self._pack_queries()
                if self._private:
                    completes = refused + completes
                    for st, _, t, _, _ in placements:
                        if t in plans and plans[t].status == "stale":
                            st.status = "stale"
                    if placements:
                        noise, fresh, n_used = \
                            self._private_query_buffers(plans)
                points = sum(take for *_, take in placements)
                sp.set_metadata(points=points)
        do_ingest, do_query = rows > 0, bool(placements)
        est = None
        if self._private:
            if do_ingest:
                self._counts, self._n = self._launch(
                    self._tick_ingest, self._counts, self._n,
                    self._flat(zbuf, zmask))
            if do_query:
                self._release_buf, est = self._launch(
                    self._tick_query_private, self._counts,
                    self._release_buf, self._flat(qbuf, qmask, noise, fresh),
                    n_used)
                for slot, plan in plans.items():
                    if plan.status == "fresh":
                        self.private_view.mark_resident(
                            self._privacy_key_of(slot))
        elif self.mesh is None:
            if do_ingest and do_query:
                self._counts, self._n, est = self._launch(
                    self._tick_full, self._counts, self._n,
                    self._flat(zbuf, zmask, qbuf, qmask))
            elif do_ingest:
                self._counts, self._n = self._launch(
                    self._tick_ingest, self._counts, self._n,
                    self._flat(zbuf, zmask))
            elif do_query:
                est = self._launch(self._tick_query, self._counts, self._n,
                                   self._flat(qbuf, qmask))
        elif do_ingest or do_query:
            # Only the halves this tick runs are shipped, each with its
            # tenant-axis sharding.
            sh_z, sh_zm, sh_q, sh_qm = self._in_shardings
            with span("storm.gw.flatten") as sp:
                zargs = (jax.device_put(zbuf, sh_z),
                         jax.device_put(zmask, sh_zm)) if do_ingest else ()
                qargs = (jax.device_put(qbuf.reshape(-1, self.dim), sh_q),
                         jax.device_put(qmask.reshape(-1), sh_qm)
                         ) if do_query else ()
                sp.set_metadata(h2d_bytes=sum(a.nbytes
                                              for a in zargs + qargs))
            if do_ingest and do_query:
                self._counts, self._n, est = self._launch(
                    self._tick_full, self._counts, self._n, *zargs, *qargs)
            elif do_ingest:
                self._counts, self._n = self._launch(
                    self._tick_ingest, self._counts, self._n, *zargs)
            else:
                est = self._launch(self._tick_query, self._counts, self._n,
                                   *qargs)
        return InflightTick(tick=self.ticks, est=est, placements=placements,
                            completes=completes, ingest_done=ingest_done,
                            rows=rows, points=points)

    @staticmethod
    def _flat(*parts) -> np.ndarray:
        """The tick's ONE fused host buffer: ``parts`` raveled end to end."""
        with span("storm.gw.flatten") as sp:
            flat = np.concatenate([p.ravel() for p in parts])
            sp.set_metadata(h2d_bytes=flat.nbytes)
        return flat

    @staticmethod
    def _launch(program, *args):
        """Call a tick program (async dispatch: its outputs are futures)."""
        with span("storm.gw.launch", program=program.__name__):
            return program(*args)

    def _run_fits(self) -> List[FitResult]:
        """Drain the fit queue against the POST-tick counters.

        Each request gathers its cohort's live counters into a sub-bank
        (widened to int32 — exact, the training dtype) and runs one
        ``erm.fit_many``: S tenants x F restarts on a single fused banked
        query stream per DFO step. The result is bit-identical to an
        offline ``erm.fit_many`` over the same counters and seed (pinned in
        ``tests/test_serve_fit.py``). Fits jit their own loss closures, so
        the tick programs' trace caches never grow here.
        """
        out: List[FitResult] = []
        while self._fit_q:
            req = self._fit_q.popleft()
            if self._private:
                out.append(self._run_private_fit(req))
            else:
                idx = jnp.asarray(req.tenants, jnp.int32)
                sub = sketch_lib.SketchBank(
                    counts=self._counts[idx].astype(jnp.int32),
                    n=self._n[idx],
                )
                out.append(run_fit_request(req, sub, self.params))
            self.fits_run += 1
        return out

    def _refused_fit(self, req: FitRequest) -> FitResult:
        s = len(req.tenants)
        self.fits_refused += 1
        return FitResult(rid=req.rid, tenants=list(req.tenants),
                         theta=np.zeros((s, self.dim), np.float32),
                         fleet_losses=np.zeros((s, req.restarts), np.float32),
                         status="refused")

    def _run_private_fit(self, req: FitRequest) -> FitResult:
        """Cohort fit from RELEASED tables only (finite policy).

        Each cohort member reads through the shared view: an open window
        rebuilds its cached release for free, a closed one charges a new
        release, an exhausted member serves its stale lane (or refuses the
        whole request — deterministic, nothing trained on partial data).
        The sub-bank is f32 released counters with release-time n, flowing
        through the UNCHANGED ``erm.fit_many`` spine — the query gather
        widens to f32 regardless, so privatized tables train as-is.
        """
        shape = (self.params.rows, self.params.buckets)
        tables, ns = [], []
        stale = False
        for slot in req.tenants:
            key = self._privacy_key_of(slot)
            plan = self.private_view.plan_read(
                key, self._rows_of[key], shape, paired=self.paired)
            if plan.status == "refuse":
                return self._refused_fit(req)
            if plan.status == "fresh":
                tables.append(self._counts[slot].astype(jnp.float32)
                              + jnp.asarray(plan.noise))
            else:
                stale = True
                tables.append(self._release_buf[slot])
            ns.append(plan.n)
        sub = sketch_lib.SketchBank(counts=jnp.stack(tables),
                                    n=jnp.asarray(ns, jnp.int32))
        res = run_fit_request(req, sub, self.params)
        if stale:
            res.status = "stale"
        return res

    def tick_finish(self, inflight: InflightTick) -> TickReport:
        """Read back one dispatched tick's estimates and report completions.

        The ``np.asarray(est)`` here is the ONLY device->host sync in the
        serving loop; with another tick already dispatched it overlaps that
        tick's execution. Finish ticks in dispatch order — results land in
        request ``out`` buffers cumulatively across the ticks of a split
        request. Queued fit requests drain HERE, after the tick's ingest
        has landed — "between ticks" in the stage pipeline, reading the
        freshest served counters.
        """
        if inflight.est is not None:
            with span("storm.gw.readback", d2h_bytes=inflight.est.nbytes):
                losses = np.asarray(inflight.est).reshape(self.tenants,
                                                          self.query_slots)
        results: List[QueryResult] = []
        if inflight.placements or inflight.completes:
            with span("storm.gw.scatter"):
                for st, req_off, t, slot_off, take in inflight.placements:
                    st.out[req_off:req_off + take] = \
                        losses[t, slot_off:slot_off + take]
                results = [QueryResult(st.req.rid, st.req.tenant, st.out,
                                       status=st.status)
                           for st in inflight.completes]
                # The finished requests' bookkeeping is released here, in
                # the span, not wherever the caller drops the tick: at
                # serving sizes that takes about a millisecond.
                inflight.placements.clear()
                inflight.completes.clear()
        self.rows_ingested += inflight.rows
        self.points_served += inflight.points
        fits: List[FitResult] = []
        if self._fit_q:
            with span("storm.gw.fits", fits=len(self._fit_q)):
                fits = self._run_fits()
        return TickReport(tick=inflight.tick, results=results,
                          rows_ingested=inflight.rows,
                          points_served=inflight.points,
                          ingest_done=inflight.ingest_done,
                          fits=fits)

    def tick(self) -> TickReport:
        """Run one engine tick synchronously: fused banked ingest, then
        fused banked query, then block for the results.

        Exactly ``tick_finish(tick_start())`` — the depth-1 degenerate case
        of the pipelined loop, kept as the simple API and the A/B baseline.
        Dispatches one of the three fixed programs by which halves carry
        traffic; an idle tick is a host-side no-op. Queries packed into a
        mixed tick read the post-ingest counters (read-your-writes).
        """
        return self.tick_finish(self.tick_start())

    def run_until_idle(self, max_ticks: int = 10_000, *,
                       pipelined: bool = False,
                       depth: int = 2) -> List[QueryResult]:
        """Tick until every pending request is served; returns all results.

        ``pipelined=True`` drains with up to ``depth`` ticks in flight
        (double-buffered: pack tick t+1 while tick t runs) — bit-identical
        results and counters, better wall-clock. On budget exhaustion
        raises :class:`TickBudgetExceeded` carrying the results that DID
        complete.
        """
        out: List[QueryResult] = []
        if pipelined:
            inflight: Deque[InflightTick] = deque()
            while self.pending or inflight:
                while self.pending and len(inflight) < depth and \
                        max_ticks > 0:
                    inflight.append(self.tick_start())
                    max_ticks -= 1
                if not inflight:
                    break  # pending traffic but no tick budget left
                out.extend(self.tick_finish(inflight.popleft()).results)
        else:
            while self.pending and max_ticks > 0:
                out.extend(self.tick().results)
                max_ticks -= 1
        if self.pending:
            raise TickBudgetExceeded(self.pending, out)
        return out
