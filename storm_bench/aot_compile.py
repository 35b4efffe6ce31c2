"""Compiles each configuration's three tick programs for a described TPU
v5e, without a chip, and prints what the compiler says of their memory.

    JAX_PLATFORMS=cpu python3 storm_bench/aot_compile.py [config ...]

The chip's compiler refuses what interpret mode accepts (blocks that do not
tile, more scoped VMEM than a kernel may ask for), so a configuration is
compiled here before its first run on the chip.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core import lsh  # noqa: E402
from repro.serve.storm_gateway import StormGateway  # noqa: E402
from storm_bench.cells import HERE, load_json  # noqa: E402
from storm_bench.run import hyperplanes  # noqa: E402


def compile_ticks(name: str, chip) -> None:
    cfg = load_json(HERE / "configs" / f"{name}.json")
    s, r, p = cfg["tenants"], cfg["rows"], cfg["planes"]
    dim = cfg["features"] + 1
    gw = StormGateway(lsh.LSHParams(projections=hyperplanes(cfg)), s, query_slots=cfg["query_slots"],
                      ingest_slots=cfg["ingest_slots"],
                      count_dtype=jnp.dtype(cfg["count_dtype"]),
                      mode="kernel")
    ingest = s * cfg["ingest_slots"] * (dim + 1)
    query = s * cfg["query_slots"] * (dim + 1)
    bank = [jax.ShapeDtypeStruct((s, r, 1 << p), jnp.dtype(cfg["count_dtype"]),
                                 sharding=chip),
            jax.ShapeDtypeStruct((s,), jnp.int32, sharding=chip)]
    for prog, flat in (("full", ingest + query), ("ingest", ingest),
                       ("query", query)):
        fn = getattr(gw, f"_tick_{prog}")
        compiled = fn.lower(*bank, jax.ShapeDtypeStruct(
            (flat,), jnp.float32, sharding=chip)).compile()
        mem = compiled.memory_analysis()
        assert "tpu_custom_call" in compiled.as_text()
        print(f"{name} {prog}: compiled; temp {mem.temp_size_in_bytes} B, "
              f"args {mem.argument_size_in_bytes} B, "
              f"out {mem.output_size_in_bytes} B", flush=True)


def main() -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in sys.argv[1:] or ["parkinsons-r2048", "airfoil-r512"]:
        compile_ticks(name, chip)


if __name__ == "__main__":
    main()
