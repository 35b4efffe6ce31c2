"""Compiles each configuration's tick programs for a described TPU v5e,
without a chip, and prints what the compiler says of their memory.

    JAX_PLATFORMS=cpu python3 storm_bench/aot_compile.py [--chips N] \\
        [config ...]

The programs are built by the configuration's deployment module
(``deployments/<name>.py``, its ``compile_ticks``), as a cell builds them:
on one described chip with ``--chips 1``, with the bank split over ``N``
chips of a described ``v5e:2x2`` otherwise. The chip's compiler refuses
what interpret mode accepts (blocks that do not tile, more scoped VMEM than
a kernel may ask for), so a configuration is compiled here before its first
run on the chip.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402

from storm_bench import cells  # noqa: E402


def compile_ticks(name: str, devices) -> None:
    cfg = cells.load_json(cells.HERE / "configs" / f"{name}.json")
    module = cells.deployment(cfg.get("deployment"))
    for prog, compiled in module.compile_ticks(cfg, devices):
        mem = compiled.memory_analysis()
        assert "tpu_custom_call" in compiled.as_text()
        print(f"{name} {prog} on {len(devices)} chips: compiled; temp "
              f"{mem.temp_size_in_bytes} B, args "
              f"{mem.argument_size_in_bytes} B, out "
              f"{mem.output_size_in_bytes} B (per chip)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("configs", nargs="*",
                    default=["parkinsons-r2048", "airfoil-r512"])
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in args.configs:
        compile_ticks(name, topo.devices[:args.chips])


if __name__ == "__main__":
    main()
