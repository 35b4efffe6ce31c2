"""Ahead-of-time compiles of the STORM kernels for a TPU v5e, without a chip.

Interpret mode runs the kernels' arithmetic on the CPU but not the chip's
compiler, which refuses what interpret mode accepts: unaligned blocks,
unsupported reshapes and contractions, more scoped VMEM than a kernel may
use. Each test compiles one entry point of ``repro.kernels.ops`` (or a whole
gateway tick) for a described ``v5e:2x2`` topology at the serving widths —
256 tenants, R=2048, p=4, sketch dim 22 — and asserts that the Pallas kernel
is in the compiled program.

The topology is described inside a fixture: only the worker that runs these
tests loads the TPU compiler, and every worker collects the same tests.
"""

import os
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import lsh
from repro.kernels import ops
from repro.serve.storm_gateway import StormGateway

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from storm_bench import layers, trace  # noqa: E402

S, R, P_, DIM, N, I_SLOTS, Q_SLOTS = 256, 2048, 4, 22, 512, 512, 64
B = 1 << P_
D_AUG = DIM + 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_case(name, one_chip):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = spec((P_, D_AUG, R))
    cases = {
        "srp_hash": (lambda x, w: ops.srp_hash(x, w, mode="kernel"),
                     spec((N, D_AUG)), w),
        "hash_histogram": (
            lambda x, w, m: ops.hash_histogram(x, w, m, mode="kernel"),
            spec((N, D_AUG)), w, spec((N,))),
        "paired_hash_histogram": (
            lambda z, w, m: ops.paired_hash_histogram(z, w, m, mode="kernel"),
            spec((N, DIM)), w, spec((N,))),
        "hash_histogram_banked": (
            lambda x, w, m: ops.hash_histogram_banked(x, w, m, mode="kernel"),
            spec((S, N, D_AUG)), w, spec((S, N))),
        "paired_hash_histogram_banked": (
            lambda z, w, m: ops.paired_hash_histogram_banked(
                z, w, m, mode="kernel"),
            spec((S, N, DIM)), w, spec((S, N))),
        "paired_hash_histogram_banked_int16": (
            lambda z, w, m: ops.paired_hash_histogram_banked(
                z, w, m, mode="kernel", out_dtype=jnp.int16),
            spec((S, N, DIM)), w, spec((S, N))),
        "sketch_query_m45": (
            lambda q, w, c: ops.sketch_query(q, w, c, mode="kernel"),
            spec((45, D_AUG)), w, spec((R, B), jnp.int32)),
        "sketch_query_m4096": (
            lambda q, w, c: ops.sketch_query(q, w, c, mode="kernel"),
            spec((4096, D_AUG)), w, spec((R, B), jnp.int32)),
        "sketch_query_banked": (
            lambda q, w, c, i: ops.sketch_query(q, w, c, mode="kernel",
                                                sketch_idx=i),
            spec((S * Q_SLOTS, D_AUG)), w, spec((S, R, B), jnp.int32),
            spec((S * Q_SLOTS,), jnp.int32)),
        "sketch_query_banked_int16": (
            lambda q, w, c, i: ops.sketch_query(q, w, c, mode="kernel",
                                                sketch_idx=i),
            spec((S * Q_SLOTS, D_AUG)), w, spec((S, R, B), jnp.int16),
            spec((S * Q_SLOTS,), jnp.int32)),
        # p = 1: the classification margin sketch (core/classification.py).
        "hash_histogram_p1": (
            lambda x, w, m: ops.hash_histogram(x, w, m, mode="kernel"),
            spec((N, D_AUG)), spec((1, D_AUG, R)), spec((N,))),
        "sketch_query_p1": (
            lambda q, w, c: ops.sketch_query(q, w, c, mode="kernel"),
            spec((45, D_AUG)), spec((1, D_AUG, R)), spec((R, 2), jnp.int32)),
    }
    return cases[name]


KERNEL_CASES = [
    "srp_hash", "hash_histogram", "paired_hash_histogram",
    "hash_histogram_banked", "paired_hash_histogram_banked",
    "paired_hash_histogram_banked_int16", "sketch_query_m45",
    "sketch_query_m4096", "sketch_query_banked", "sketch_query_banked_int16",
    "hash_histogram_p1", "sketch_query_p1",
]


@pytest.mark.parametrize("name", KERNEL_CASES)
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, *args = _kernel_case(name, one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("s,r,q,dtype", [
    (S, R, Q_SLOTS, jnp.int32),   # this file's serving widths
    (S, R, Q_SLOTS, jnp.int8),
    (1024, 2048, 16, jnp.int32),  # parkinsons-r2048
    (8192, 512, 16, jnp.int32),   # past the one-hot query's scoped VMEM
])
def test_tenant_major_query_compiles_for_v5e(s, r, q, dtype, one_chip):
    """One query custom call, named as the benchmark's trace reader finds
    it, and no gather of the bank beside it."""
    def spec(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    text = _compiled_text(
        lambda x, w, c: ops.sketch_query(x, w, c, mode="kernel",
                                         points_per_table=q),
        spec((s * q, D_AUG)), spec((P_, D_AUG, r)), spec((s, r, B), dtype))
    instructions = [[line.strip(), 0, 1] for line in text.splitlines()
                    if line.strip().startswith(("%", "ROOT %"))]
    assert trace.kernel_ns(instructions, layers.KERNELS["query"], 0, 1) == (1.0, 1)
    assert "tpu_custom_call" in text and " gather(" not in text


@pytest.fixture(scope="module")
def params():
    return lsh.init_srp(jax.random.PRNGKey(0), R, P_, D_AUG)


def test_gateway_tick_compiles_for_one_chip(params, one_chip):
    """The meshless full tick (ingest + query in one program)."""
    gw = StormGateway(params, S, query_slots=Q_SLOTS, ingest_slots=I_SLOTS,
                      mode="kernel")
    flat = S * (I_SLOTS + Q_SLOTS) * (DIM + 1)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((S, R, B), jnp.int32), ((S,), jnp.int32),
                                 ((flat,), jnp.float32))]
    assert "tpu_custom_call" in gw._tick_full.lower(*args).compile().as_text()


def test_gateway_tick_compiles_for_bank_mesh(params, topo):
    """The full tick with the bank split over four chips: the kernels run
    inside ``jax.shard_map`` with its varying-axes check on."""
    mesh = Mesh(np.array(topo.devices), ("bank",))
    gw = StormGateway(params, S, query_slots=Q_SLOTS, ingest_slots=I_SLOTS,
                      mode="kernel", mesh=mesh, axis="bank")
    bank = NamedSharding(mesh, P("bank"))
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=bank)
            for shape, dtype in (((S, R, B), jnp.int32), ((S,), jnp.int32),
                                 ((S, I_SLOTS, DIM), jnp.float32),
                                 ((S, I_SLOTS), jnp.float32),
                                 ((S * Q_SLOTS, DIM), jnp.float32),
                                 ((S * Q_SLOTS,), jnp.float32))]
    assert "tpu_custom_call" in gw._tick_full.lower(*args).compile().as_text()
