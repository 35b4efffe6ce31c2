"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Optional mesh layout for 1000+-node scale (DESIGN.md §5): stages own
contiguous layer groups; microbatches stream through with a steady-state
rotation implemented as ``collective_permute`` along the ``pipe`` axis.
This module is deliberately model-agnostic — any ``fn(stage_params, x)``
block function works — and is demonstrated/tested on a toy 4-stage mesh
(``tests/test_pipeline.py``); the required production dry-run mesh stays
DP x TP per the assignment.

Schedule: with S stages and M microbatches, step t processes microbatch
``t - stage`` on each stage (bubble fraction (S-1)/(M+S-1), standard GPipe).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def pipeline_forward(
    fn: Callable[[jax.Array, Array], Array],
    stage_params: Array,      # leading dim == number of stages (sharded on pipe)
    x: Array,                 # (M, micro_batch, ...) microbatches
    mesh: Mesh,
    axis: str = "pipe",
) -> Array:
    """Run ``x`` through all pipeline stages. Returns the final activations.

    ``fn(params_for_stage, microbatch)`` applies one stage's layers.
    """
    n_stage = mesh.shape[axis]
    m = x.shape[0]
    assert m >= 1

    def stage_fn(params_local, x_local):
        # params_local: (1, ...) this stage's params; x_local: (M, mb, ...)
        # on stage 0 holds the microbatch stream, others start with zeros.
        stage = jax.lax.axis_index(axis)
        params_here = jax.tree.map(lambda p: p[0], params_local)
        steps = m + n_stage - 1

        def body(carry, t):
            buf, outputs = carry
            # which microbatch this stage sees at step t (GPipe diagonal)
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < m)
            # stage 0 injects from its local stream; others take the rotated buf
            inject = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(mb_idx, 0, m - 1), axis=0, keepdims=False
            )
            inp = jnp.where(stage == 0, inject, buf)
            out = fn(params_here, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            # last stage records finished microbatches (masked update keeps
            # the varying-manual-axes type consistent under shard_map)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(mb_idx, 0, m - 1), axis=0
            )
            outputs = jnp.where(active & (stage == n_stage - 1), updated,
                                outputs)
            # rotate activations forward one stage
            perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
            buf = jax.lax.ppermute(out, axis, perm)
            return (buf, outputs), None

        buf0 = jax.lax.pcast(jnp.zeros_like(x_local[0]), (axis,),
                             to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(x_local), (axis,), to="varying")
        (_, outputs), _ = jax.lax.scan(body, (buf0, outs0),
                                       jnp.arange(steps))
        # only the last stage holds non-zero outputs; psum broadcasts them
        return jax.lax.psum(outputs, axis)

    fn_sharded = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    stage_params = jax.device_put(
        stage_params, NamedSharding(mesh, P(axis))
    )
    return fn_sharded(stage_params, x)
