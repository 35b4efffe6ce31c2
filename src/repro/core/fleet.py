"""Shared fleet machinery for every STORM driver (DESIGN.md §8.4).

One fleet loop, one refine-key convention, one selection path. The three
sketch-training drivers — ``regression.fit``, ``classification.fit``,
``probes.fit_probe`` — all train ``restarts=F`` optimizers against ONE frozen
sketch by delegating here:

* :func:`make_loss_fn` — the batched sketch-loss closure with session-hoisted
  kernel weights (the ``(R, p, d) -> (p, d, R)`` transpose runs once per fit,
  never inside the scanned DFO step). Paired (PRP regression / probes) and
  single-sided (classification margin) sessions share the same builder.
* :func:`seed_fleet` — the restart-diversity schedule: member 0 is the
  driver's deterministic baseline (``restarts=1`` reproduces the single fit
  bit-for-bit); members ``i >= 1`` draw random-ball inits and walk geometric
  σ/lr ladders.
* :func:`run_fleet` — optimize-then-refine, the single owner of the
  refine-key convention (``fold_in(member_key, pass+1)``).
* :func:`select_theta` — fused final selection (all members + an optional
  zero-guard in one query), with the basin-average mode.

Keeping these in one module is what stops the drivers from growing three
hand-rolled fleet variants that drift apart (the pre-PR-3 state).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dfo, lsh, sketch as sketch_lib

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Restart-diversity and selection knobs shared by all drivers.

    The fleet *size* is not here — each driver exposes its own ``restarts``
    so ``FleetConfig()`` defaults never change a single-fit call's meaning.
    """

    select: str = "best"          # best | average (basin average, §8.2)
    basin_tol: float = 0.05       # average: keep members within (1+tol)·best
    sigma_spread: float = 2.0     # geometric σ ladder across members
    lr_spread: float = 2.0        # geometric lr ladder (reverse-paired)
    init_scale: float = 0.3       # random-ball init radius, members >= 1


def config_from_restarts(config) -> FleetConfig:
    """Adapt a driver config's flat ``restart_*`` fields to a FleetConfig.

    Duck-typed over the field names every driver config shares
    (``restart_select``, ``restart_basin_tol``, ``restart_sigma_spread``,
    ``restart_lr_spread``, ``restart_init_scale``) — one adapter, so a new
    fleet knob lands in every driver or none.
    """
    return FleetConfig(
        select=config.restart_select,
        basin_tol=config.restart_basin_tol,
        sigma_spread=config.restart_sigma_spread,
        lr_spread=config.restart_lr_spread,
        init_scale=config.restart_init_scale,
    )


def validate_select(select: str) -> None:
    """Fail fast on a selection-mode typo, before minutes of training."""
    if select not in ("best", "average"):
        raise ValueError(f"unknown restart_select {select!r}; "
                         "use best | average")


def member_point_idx(member_map: Array, q: int) -> Array:
    """Per-point sketch index for a member-major ``(q, ...)`` batch.

    Single owner of the member-major routing rule (DESIGN.md §9): a batch of
    ``q`` points laid out as F contiguous per-member blocks routes row ``i``
    to ``member_map[i // (q // F)]``. Shared by the banked loss closures here
    and the serving gateway's tick (``serve.storm_gateway``), whose
    tenant-major slot layout is exactly ``member_map = arange(S)``.
    """
    f = member_map.shape[0]
    if q % f:
        raise ValueError(
            f"banked batch of {q} points is not member-major over "
            f"{f} fleet members"
        )
    return jnp.repeat(member_map, q // f)


def make_loss_fn(
    sk,
    params: lsh.LSHParams,
    paired: bool = True,
    scale: float = 1.0,
    l2: float = 0.0,
    engine: str = "auto",
    d: Optional[int] = None,
    member_map: Optional[Array] = None,
    transform: Optional[Callable[[Array], Array]] = None,
) -> Callable[[Array], Array]:
    """Batched sketch-loss closure with session-hoisted kernel weights.

    The kernel path's ``(R, p, d) -> (p, d, R)`` weight transpose
    (``ops.from_lsh_params``) runs ONCE here, outside every query; the
    returned closure threads the converted array through each call, so the
    scanned DFO step contains no per-step transpose of the projection tensor
    (jaxpr-asserted in tests). The kernel's m-tiled query grid accepts any
    batch size, so DFO sphere blocks, fleet blocks of ``F*(2k+1)`` points,
    and O(d^2) quadratic-refine batches all stay on the fused path.

    Args:
      sk: the (frozen) sketch to query — a lone :class:`~.sketch.Sketch`, or
        a :class:`~.sketch.SketchBank` for *banked* sessions (DESIGN.md §9)
        where the fleet spans S tenants' sketches at once.
      params: hash parameters (one family — shared by the whole bank).
      paired: PRP sketch (regression/probes) vs single-sided (classification
        margin loss) — controls the ``2n`` vs ``n`` estimator denominator.
      scale: constant multiplier on the estimate (classification's Thm-3
        ``2**p`` factor); 1.0 leaves the estimate untouched.
      l2: optional ridge on the first ``d`` coordinates (paper §6).
      engine: ``scan | kernel | auto`` query path (DESIGN.md §3.4).
      d: feature dimension for the ridge term; defaults to ``params.dim - 3``
        (params hash the augmented ``[x, y]`` space of ``d + 1 + 2`` dims).
      transform: optional elementwise monotone map on the scaled estimate
        (a registered surrogate's ``transform``, e.g. ``log1p`` for the
        exp-concave logistic objective); applied before the ridge so the
        regularizer stays additive. ``None`` leaves the estimate untouched.
      member_map: required with a ``SketchBank`` — ``(F,)`` int32 mapping
        fleet member ``f`` to its sketch index. The closure then requires
        member-major batches whose size is a multiple of ``F`` (every fused
        caller — ``minimize_fleet``'s ``(F, 2k+1)`` flatten,
        ``quadratic_refine_fleet``'s ``(F, m)`` and ``(F, 2)`` blocks,
        :func:`select_theta_many`'s ``(S, C)`` candidates — already is) and
        routes each point to ``member_map[row // (batch // F)]``.

    Returns:
      A jitted ``(q, dim) -> (q,)`` loss callable.
    """
    d = params.dim - 3 if d is None else d
    banked = isinstance(sk, sketch_lib.SketchBank)
    if banked != (member_map is not None):
        raise ValueError("member_map must be given iff sk is a SketchBank")
    if banked and sk.counts.shape[0] == 1:
        # A 1-sketch bank runs the lone-sketch program LITERALLY — the
        # "S = 1 is a bit-identical slice of today's API" guarantee
        # (DESIGN.md §9). The banked gather's identical values survive, but
        # its different graph shape lets XLA fuse the downstream (inexact)
        # gradient einsum differently inside the scanned DFO step — ~1-ULP
        # trace drift per step. Slicing keeps the compiled program itself
        # unchanged, and skips the pointless per-point index.
        sk = sk.select(0)
        banked = False
        member_map = None
    use_kernel = sketch_lib.resolve_engine(engine) == "kernel"

    def point_idx(thetas: Array) -> Array:
        """Per-point sketch index for a member-major (q, dim) batch."""
        if thetas.ndim != 2:
            raise ValueError("banked loss closures need (q, dim) batches")
        return member_point_idx(member_map, thetas.shape[0])

    if use_kernel:
        from repro.kernels import ops as kernel_ops  # deferred: ops imports core

        w = kernel_ops.from_lsh_params(params)  # hoisted: once per session

        def estimate(thetas: Array) -> Array:
            idx = point_idx(thetas) if banked else None
            return kernel_ops.query_theta_with_weights(sk, w, thetas,
                                                       paired=paired,
                                                       sketch_idx=idx)
    else:

        def estimate(thetas: Array) -> Array:
            if banked:
                return sketch_lib.query_theta_banked(
                    sk, params, thetas, point_idx(thetas), paired=paired
                )
            return sketch_lib.query_theta(sk, params, thetas, paired=paired)

    def loss_fn(thetas: Array) -> Array:  # (q, dim) -> (q,)
        est = estimate(thetas)
        if scale != 1.0:
            est = scale * est
        if transform is not None:
            est = transform(est)
        if l2 > 0.0:
            est = est + l2 * lsh.row_sq_norm(thetas[..., :d])
        return est

    return jax.jit(loss_fn)


def seed_fleet(
    key: Array,
    f: int,
    dim: int,
    base: dfo.DFOConfig,
    config: Optional[FleetConfig] = None,
    theta0: Optional[Array] = None,
) -> Tuple[Array, Array, Array, Array]:
    """Restart-diversity schedule (DESIGN.md §8.2), shared by all drivers.

    Member 0 is the driver's deterministic baseline — ``theta0`` (the
    driver's single-fit init; zeros when omitted) with the configured σ/lr
    and ``key`` itself — so ``restarts=1`` reproduces the single-iterate fit
    bit-for-bit. Members ``i >= 1`` draw random-ball inits around ``theta0``
    and walk geometric σ/lr ladders (reverse-paired so aggressive radii meet
    conservative rates and vice versa), covering basins and noise regimes the
    baseline member misses.

    Args:
      key: the driver's DFO key (member 0 uses it verbatim).
      f: fleet size F.
      dim: full iterate dimension (regression/probes: ``d + 1``;
        classification: ``d``).
      base: the shared DFO config (σ/lr for member 0).
      config: diversity knobs (spreads, init radius).
      theta0: ``(dim,)`` baseline init; defaults to zeros.

    Returns:
      ``(keys (F,), theta0 (F, dim), sigmas (F,), lrs (F,))``.
    """
    config = config or FleetConfig()
    base_theta = (jnp.zeros((dim,), jnp.float32) if theta0 is None
                  else theta0.astype(jnp.float32))
    keys = [key]
    inits = [base_theta]
    sigmas = [jnp.float32(base.sigma)]
    lrs = [jnp.float32(base.learning_rate)]
    for i in range(1, f):
        # Offset past the refine-pass fold_in indices (1..refine_steps).
        ki = jax.random.fold_in(key, 7919 + i)
        keys.append(ki)
        u = -1.0 + 2.0 * (i - 1) / max(1, f - 2) if f > 2 else 0.0
        sigmas.append(jnp.float32(base.sigma * config.sigma_spread ** u))
        lrs.append(jnp.float32(base.learning_rate
                               * config.lr_spread ** (-u)))
        inits.append(
            base_theta
            + config.init_scale
            * jax.random.normal(jax.random.fold_in(ki, 0), (dim,), jnp.float32)
        )
    return (jnp.stack(keys), jnp.stack(inits), jnp.stack(sigmas),
            jnp.stack(lrs))


def tenant_key(key: Array, s: int) -> Array:
    """Per-tenant PRNG convention for banked fits (DESIGN.md §9).

    Tenant 0 uses the driver's key VERBATIM — so ``fit_many`` with ``S = 1``
    seeds exactly like the single-tenant ``fit`` — and tenant ``s >= 1``
    folds in ``s``. One owner, so every ``fit_many`` driver keys its tenants
    identically.
    """
    return key if s == 0 else jax.random.fold_in(key, s)


def seed_fleet_many(
    key: Array,
    s: int,
    f: int,
    dim: int,
    base: dfo.DFOConfig,
    config: Optional[FleetConfig] = None,
    theta0: Optional[Array] = None,
) -> Tuple[Array, Array, Array, Array]:
    """Seed S per-tenant restart fleets into one member-major block.

    Tenant ``t`` runs :func:`seed_fleet` under :func:`tenant_key` — its F
    members occupy rows ``[t*F, (t+1)*F)`` (member-major, matching the
    ``member_map = repeat(arange(S), F)`` convention of banked loss
    closures). ``theta0`` may be ``(S, dim)`` for per-tenant baseline inits
    (classification) or ``None`` for the shared zero baseline.

    Returns:
      ``(keys (S*F,), theta0 (S*F, dim), sigmas (S*F,), lrs (S*F,))``.
    """
    parts = [
        seed_fleet(tenant_key(key, t), f, dim, base, config,
                   theta0=None if theta0 is None else theta0[t])
        for t in range(s)
    ]
    return tuple(
        jnp.concatenate([p[i] for p in parts], axis=0) for i in range(4)
    )


def run_fleet(
    loss_fn: Callable[[Array], Array],
    theta0: Array,
    keys: Array,
    config: dfo.DFOConfig,
    project: Optional[Callable[[Array], Array]] = None,
    sigma: Optional[Array] = None,
    learning_rate: Optional[Array] = None,
    refine_steps: int = 0,
    refine_radius: float = 0.3,
) -> dfo.FleetDFOResult:
    """Optimize-then-refine fleet loop shared by every driver and
    ``distributed.fleet_fit`` — the single owner of the refine-key convention
    (``fold_in(member_key, pass+1)``) and the radius-halving schedule, so the
    sharded and restart paths cannot drift apart.

    Returns the refined ``(F, dim)`` thetas with the minimize-phase loss
    traces.
    """
    res = dfo.minimize_fleet(loss_fn, theta0, keys, config, project=project,
                             sigma=sigma, learning_rate=learning_rate)
    thetas = res.theta
    for i in range(refine_steps):
        refine_keys = jax.vmap(lambda mk: jax.random.fold_in(mk, i + 1))(keys)
        thetas = dfo.quadratic_refine_fleet(
            loss_fn, thetas, refine_keys,
            radius=refine_radius / (2.0 ** i), project=project,
        )
    return dfo.FleetDFOResult(theta=thetas, losses=res.losses)


def select_theta(
    loss_fn: Callable[[Array], Array],
    thetas: Array,
    traces: Array,
    select: str = "best",
    basin_tol: float = 0.05,
    guard: Optional[Array] = None,
    project: Optional[Callable[[Array], Array]] = None,
) -> Tuple[Array, Array, Array]:
    """Fused final selection: all members (+ optional guard) in ONE query.

    Args:
      loss_fn: the fused sketch loss.
      thetas: ``(F, dim)`` final fleet iterates.
      traces: ``(F, steps)`` per-member loss traces.
      select: ``best`` (arg-min) or ``average`` (basin average: mean the
        members within ``(1 + basin_tol)``·best — averaging across one basin
        cuts frozen-hash noise, while the arg-min gate keeps stray basins
        out; the best member rides in the runoff so an average straddling
        two basins can never displace a strictly better single iterate).
      guard: optional ``(dim,)`` fallback candidate (regression/probes use
        the projected zero — keep theta=0 if frozen-hash noise drove every
        member to a worse-than-trivial model). ``None`` for scale-free
        drivers (classification) where theta=0 is meaningless.
      project: projection for the basin average (kept on the constraint set).

    Returns:
      ``(theta_tilde, trace, fleet_vals)`` — the selected iterate, the loss
      trace of the member the selection measured against, and the ``(F,)``
      final sketch-loss per member.
    """
    f = thetas.shape[0]
    proj = project if project is not None else (lambda t: t)
    cand = thetas if guard is None else jnp.concatenate(
        [thetas, guard[None, :]], axis=0
    )
    vals = loss_fn(cand)
    fleet_vals = vals[:f]
    best_member = jnp.argmin(fleet_vals)
    if f > 1 and select == "average":
        best = jnp.min(fleet_vals)
        keep = (fleet_vals <= best * (1.0 + basin_tol) + 1e-12)
        avg = proj(
            jnp.sum(jnp.where(keep[:, None], thetas, 0.0), axis=0)
            / jnp.maximum(jnp.sum(keep.astype(jnp.float32)), 1.0)
        )
        runoff_rows = [avg, thetas[best_member]]
        if guard is not None:
            runoff_rows.append(cand[-1])
        runoff = jnp.stack(runoff_rows)
        runoff_vals = loss_fn(runoff)
        # Break exact ties toward the average (index 0): jnp.argmin already
        # prefers the lowest index, so the noise-reduced mean wins a draw.
        theta_tilde = runoff[jnp.argmin(runoff_vals)]
        trace = traces[best_member]
    else:
        idx = jnp.argmin(vals)
        theta_tilde = cand[idx]
        # Trace follows the selected member; if the guard won, report the
        # best member's trace (the run the selection measured it against).
        trace = traces[jnp.where(idx < f, idx, best_member)]
    return theta_tilde, trace, fleet_vals


def select_theta_many(
    loss_fn: Callable[[Array], Array],
    thetas: Array,
    traces: Array,
    select: str = "best",
    basin_tol: float = 0.05,
    guard: Optional[Array] = None,
    project: Optional[Callable[[Array], Array]] = None,
) -> Tuple[Array, Array, Array]:
    """Per-tenant :func:`select_theta` for a banked fleet, fully fused.

    All S tenants' candidates (each tenant's F members + its optional guard
    row) go through ONE banked loss call — ``loss_fn`` must be a banked
    closure built with ``member_map = arange(S)`` so each tenant's candidate
    block reads that tenant's own sketch. ``S = 1`` reproduces
    :func:`select_theta` bit-for-bit (same candidate batch, same values,
    same arg-min).

    Args:
      loss_fn: banked selection loss (``member_map = arange(S)``).
      thetas: ``(S, F, dim)`` final fleet iterates, tenant-major.
      traces: ``(S, F, steps)`` per-member loss traces.
      select / basin_tol / guard / project: as :func:`select_theta`; the
        guard is one shared ``(dim,)`` fallback evaluated per tenant.

    Returns:
      ``(theta (S, dim), trace (S, steps), fleet_vals (S, F))``.
    """
    s, f, dim = thetas.shape
    proj = project if project is not None else (lambda t: t)
    rows = jnp.arange(s)
    if guard is None:
        cand = thetas
    else:
        cand = jnp.concatenate(
            [thetas, jnp.broadcast_to(guard, (s, 1, dim))], axis=1
        )
    vals = loss_fn(cand.reshape(s * cand.shape[1], dim))
    vals = vals.reshape(s, cand.shape[1])
    fleet_vals = vals[:, :f]
    best_member = jnp.argmin(fleet_vals, axis=1)  # (S,)
    if f > 1 and select == "average":
        best = jnp.min(fleet_vals, axis=1, keepdims=True)
        keep = fleet_vals <= best * (1.0 + basin_tol) + 1e-12  # (S, F)
        avg = proj(
            jnp.sum(jnp.where(keep[:, :, None], thetas, 0.0), axis=1)
            / jnp.maximum(jnp.sum(keep.astype(jnp.float32), axis=1,
                                  keepdims=True), 1.0)
        )
        runoff_rows = [avg, thetas[rows, best_member]]
        if guard is not None:
            runoff_rows.append(cand[:, -1])
        runoff = jnp.stack(runoff_rows, axis=1)  # (S, 2 or 3, dim)
        runoff_vals = loss_fn(runoff.reshape(-1, dim))
        runoff_vals = runoff_vals.reshape(s, runoff.shape[1])
        # Ties break toward the average (index 0), as in select_theta.
        theta = runoff[rows, jnp.argmin(runoff_vals, axis=1)]
        trace = traces[rows, best_member]
    else:
        idx = jnp.argmin(vals, axis=1)  # (S,)
        theta = cand[rows, idx]
        trace = traces[rows, jnp.where(idx < f, idx, best_member)]
    return theta, trace, fleet_vals
