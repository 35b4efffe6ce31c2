"""Distributed STORM: shard-local sketching, collective merge, fleet training.

The sketch's mergeability-by-addition maps exactly onto ``psum``: every
data-parallel worker folds its local stream into a private sketch and one
integer all-reduce produces the sketch of the union (DESIGN.md §3). At a few
KB–MB the sketch is negligible against ICI bandwidth, so the paper's
communication-efficiency claim survives verbatim at pod scale.

Entry points:

* :func:`sharded_sketch` — SPMD build + merge under ``shard_map`` for data
  already sharded across a mesh axis (the production path).
* :func:`tree_merge` — host-side hierarchical merge of independently built
  sketches (the paper's edge-gateway topology).
* :func:`fleet_fit` — the training-side dual: shard a FLEET of optimizers
  over the mesh against one replicated merged sketch. Counters are read-only
  during optimization, so after the one-time merge there is **zero per-step
  communication** — a gateway trains many edge models from one sketch
  (DESIGN.md §8).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dfo, erm, lsh, sketch as sketch_lib

Array = jax.Array


def sharded_sketch(
    params: lsh.LSHParams,
    z: Array,
    mesh: Mesh,
    axis: str | Sequence[str] = "data",
    paired: bool = True,
    batch: int = 256,
) -> sketch_lib.Sketch:
    """Build one merged sketch from data sharded over ``axis``.

    Args:
      params: hash parameters (replicated on every device).
      z: ``(n, dim)`` pre-scaled examples, shardable on dim 0 by ``axis``.
      mesh: the device mesh.
      axis: mesh axis (or axes) holding the data shards.
      paired: PRP (regression) vs plain (classification) inserts.

    Returns:
      The merged sketch, replicated across the mesh.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def local_build(p: lsh.LSHParams, z_local: Array) -> sketch_lib.Sketch:
        sk = sketch_lib.sketch_dataset(
            p, z_local, batch=batch, paired=paired, vary_axes=axes
        )
        counts = sk.counts
        n = sk.n
        for ax in axes:  # integer all-reduce == sketch merge
            counts = jax.lax.psum(counts, ax)
            n = jax.lax.psum(n, ax)
        return sketch_lib.Sketch(counts=counts, n=n)

    shard_spec = P(axes)
    fn = jax.shard_map(
        local_build,
        mesh=mesh,
        in_specs=(P(), shard_spec),
        out_specs=P(),
    )
    z = jax.device_put(z, NamedSharding(mesh, shard_spec))
    return fn(params, z)


def tree_merge(sketches: Sequence[sketch_lib.Sketch]) -> sketch_lib.Sketch:
    """Pairwise (associative) merge — the edge-gateway aggregation topology."""
    layer = list(sketches)
    while len(layer) > 1:
        nxt = [
            sketch_lib.merge(layer[i], layer[i + 1])
            for i in range(0, len(layer) - 1, 2)
        ]
        if len(layer) % 2:
            nxt.append(layer[-1])
        layer = nxt
    return layer[0]


def fleet_fit(
    sk: sketch_lib.Sketch,
    params: lsh.LSHParams,
    theta0: Array,
    keys: Array,
    config: dfo.DFOConfig,
    mesh: Optional[Mesh] = None,
    axis: str = "fleet",
    sigma: Optional[Union[float, Array]] = None,
    learning_rate: Optional[Union[float, Array]] = None,
    refine_steps: int = 0,
    refine_radius: float = 0.3,
    l2: float = 0.0,
    engine: str = "auto",
    project_last: bool = True,
) -> dfo.FleetDFOResult:
    """Train F models against ONE replicated sketch, fleet axis over the mesh.

    The communication dual of :func:`sharded_sketch`: there the *data* shards
    and the sketch is the reduction; here the merged sketch replicates
    (read-only counters) and the *fleet* of optimizers shards over ``axis``.
    Each device advances its fleet shard with one fused
    ``F_local * (2k+1)``-point query per DFO step and NO collectives — the
    gateway topology where many edge models train from one merged summary.

    Args:
      sk: the merged sketch (replicated to every device).
      params: hash parameters (replicated).
      theta0: ``(F, dim)`` initial iterates, shardable on dim 0.
      keys: ``(F,)`` stacked PRNG keys, one per member.
      config: shared DFO hyperparameters.
      mesh: device mesh; ``None`` runs the identical program unsharded (the
        reference semantics the 1-device-mesh test pins).
      axis: mesh axis carrying the fleet shards.
      sigma / learning_rate: optional per-member ``(F,)`` hyperparameters.
      refine_steps / refine_radius: optional quadratic-polish passes.
      l2: ridge on the sketch loss (paper §6).
      engine: query path (``scan | kernel | auto``).
      project_last: pin ``theta[..., -1] = -1`` (Algorithm 2's constraint).

    Returns:
      ``FleetDFOResult`` with ``(F, dim)`` thetas and ``(F, steps)`` traces.
    """
    f = theta0.shape[0]
    proj = dfo.pin_last_coordinate(-1.0) if project_last else None
    sig = dfo._fleet_param(sigma, config.sigma, f)
    lr = dfo._fleet_param(learning_rate, config.learning_rate, f)

    def local(counts, n, projections, th, ks, sg, lr_):
        loss_fn = erm.sketch_loss_fn(
            sketch_lib.Sketch(counts=counts, n=n),
            lsh.LSHParams(projections=projections),
            paired=True,
            l2=l2,
            engine=engine,
        )
        # Shared optimize-then-refine loop: fleet_fit members advance exactly
        # like fit() / fit_probe() restarts (same refine-key/radius schedule).
        res = erm.run_fleet(
            loss_fn, th, ks, config, project=proj, sigma=sg,
            learning_rate=lr_, refine_steps=refine_steps,
            refine_radius=refine_radius,
        )
        return res.theta, res.losses

    if mesh is None:
        # Jitted whole, like the shard_map path compiles it: the unsharded
        # reference is the same compiled program minus the sharding
        # annotations (loss traces match a 1-device mesh bit-for-bit).
        thetas, traces = jax.jit(local)(sk.counts, sk.n, params.projections,
                                        theta0, keys, sig, lr)
        return dfo.FleetDFOResult(theta=thetas, losses=traces)

    from repro.sharding import specs as sharding_specs

    fleet_spec, replicated = sharding_specs.fleet_specs(axis)
    sharding_specs.check_fleet_divisible(f, mesh, axis)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(replicated, replicated, replicated,
                  fleet_spec, fleet_spec, fleet_spec, fleet_spec),
        out_specs=(fleet_spec, fleet_spec),
    )
    put = NamedSharding(mesh, fleet_spec)
    thetas, traces = fn(
        sk.counts, sk.n, params.projections,
        jax.device_put(theta0, put), jax.device_put(keys, put),
        jax.device_put(sig, put), jax.device_put(lr, put),
    )
    return dfo.FleetDFOResult(theta=thetas, losses=traces)


def fleet_fit_banked(
    bank: sketch_lib.SketchBank,
    params: lsh.LSHParams,
    theta0: Array,
    keys: Array,
    config: dfo.DFOConfig,
    restarts_per_sketch: int,
    mesh: Optional[Mesh] = None,
    axis: str = "bank",
    sigma: Optional[Union[float, Array]] = None,
    learning_rate: Optional[Union[float, Array]] = None,
    refine_steps: int = 0,
    refine_radius: float = 0.3,
    l2: float = 0.0,
    engine: str = "auto",
    paired: bool = True,
    scale: float = 1.0,
    project_last: bool = True,
) -> dfo.FleetDFOResult:
    """Train S tenants × F restarts with the BANK axis sharded over a mesh.

    The banked extension of :func:`fleet_fit` (DESIGN.md §9): instead of one
    replicated sketch, each device owns a contiguous slice of the counter
    bank *and* exactly the fleet members mapped to those sketches
    (``sharding.specs.bank_specs`` — member-major ``(S*F, ...)`` arrays and
    the ``(S, R, B)`` bank shard the same leading axis). Members only ever
    query their own tenant's table, so after placement there is zero
    per-step communication; each device advances its tenants with one local
    fused banked query per DFO step.

    Args:
      bank: the sketch bank, shardable on its leading (sketch) axis.
      params: the shared hash family (replicated).
      theta0: ``(S*F, dim)`` member-major initial iterates (tenant t's F
        members at rows ``[t*F, (t+1)*F)`` — ``fleet.seed_fleet_many``'s
        layout).
      keys: ``(S*F,)`` stacked member PRNG keys.
      config: shared DFO hyperparameters.
      restarts_per_sketch: F — members per tenant (the member→sketch map is
        ``repeat(arange(S_local), F)`` on every device, which is what makes
        the sharded map a pure reindex of the global one).
      mesh: device mesh; ``None`` runs the identical program unsharded.
      axis: mesh axis carrying the bank shards.
      sigma / learning_rate: optional per-member ``(S*F,)`` hyperparameters.
      refine_steps / refine_radius / l2 / engine: as :func:`fleet_fit`.
      paired / scale: loss estimator shape (PRP regression/probes vs the
        single-sided ``2**p``-scaled classification margin).
      project_last: pin ``theta[..., -1] = -1`` (Algorithm 2's constraint).

    Returns:
      ``FleetDFOResult`` with ``(S*F, dim)`` thetas and traces.
    """
    s = bank.n.shape[0]
    f_total = theta0.shape[0]
    if f_total != s * restarts_per_sketch:
        raise ValueError(
            f"theta0 carries {f_total} members for {s} sketches x "
            f"{restarts_per_sketch} restarts"
        )
    proj = dfo.pin_last_coordinate(-1.0) if project_last else None
    sig = dfo._fleet_param(sigma, config.sigma, f_total)
    lr = dfo._fleet_param(learning_rate, config.learning_rate, f_total)

    def local(counts, n, projections, th, ks, sg, lr_):
        s_local = counts.shape[0]
        member_map = jnp.repeat(jnp.arange(s_local, dtype=jnp.int32),
                                restarts_per_sketch)
        loss_fn = erm.sketch_loss_fn(
            sketch_lib.SketchBank(counts=counts, n=n),
            lsh.LSHParams(projections=projections),
            paired=paired,
            scale=scale,
            l2=l2,
            engine=engine,
            member_map=member_map,
        )
        res = erm.run_fleet(
            loss_fn, th, ks, config, project=proj, sigma=sg,
            learning_rate=lr_, refine_steps=refine_steps,
            refine_radius=refine_radius,
        )
        return res.theta, res.losses

    if mesh is None:
        thetas, traces = jax.jit(local)(bank.counts, bank.n,
                                        params.projections,
                                        theta0, keys, sig, lr)
        return dfo.FleetDFOResult(theta=thetas, losses=traces)

    from repro.sharding import specs as sharding_specs

    bank_spec, replicated = sharding_specs.bank_specs(axis)
    sharding_specs.check_bank_divisible(s, mesh, axis)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(bank_spec, bank_spec, replicated,
                  bank_spec, bank_spec, bank_spec, bank_spec),
        out_specs=(bank_spec, bank_spec),
    )
    put = NamedSharding(mesh, bank_spec)
    thetas, traces = fn(
        jax.device_put(bank.counts, put), jax.device_put(bank.n, put),
        params.projections,
        jax.device_put(theta0, put), jax.device_put(keys, put),
        jax.device_put(sig, put), jax.device_put(lr, put),
    )
    return dfo.FleetDFOResult(theta=thetas, losses=traces)


@partial(jax.jit, static_argnames=("paired",))
def replicated_query(
    sk: sketch_lib.Sketch, params: lsh.LSHParams, thetas: Array, paired: bool = True
) -> Array:
    """Query a merged (replicated) sketch — every host optimizes locally."""
    return sketch_lib.query_theta(sk, params, thetas, paired=paired)
