"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own, found by name:

* ``configs/<config>.json``: the deployment's sizes;
* ``traffic/<traffic>.json``: the mix's parameters, whose ``kind`` names the
  generator ``traffic/<kind>.py``;
* ``metrics/<metric>.py``: the reader of one per-layer metric, a function
  ``read(run) -> float | None``;
* ``peaks/<device kind, spaces as _>.json``: the chip's published peaks.

Adding a cell or a metric therefore adds files and entries, and edits none.
``rehearsal.json`` holds test-only cells at tiny sizes for CPU rehearsals;
each reports the metrics of the real cell it names under ``reports_as``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    """The cell ``name`` from ``BENCHMARK.json`` (or ``rehearsal.json``),
    with its configuration and traffic loaded."""
    bench = load_json(REPO / "BENCHMARK.json")
    if rehearsal:
        bench = dict(bench, workloads=load_json(HERE / "rehearsal.json")
                     ["workloads"])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    reports = w.get("reports_as", name)  # a rehearsal cell's real cell
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(HERE / "configs" / f"{w['config']}.json"),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, reports)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, reports)],
    )


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "storm_bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str):
    """The traffic generator module ``traffic/<kind>.py``."""
    return load_module(HERE / "traffic" / f"{kind}.py")


def metric_reader(name: str) -> Callable:
    return load_module(HERE / "metrics" / f"{name}.py").read


def peaks(device_kind: str) -> dict:
    path = HERE / "peaks" / f"{device_kind.replace(' ', '_')}.json"
    if not path.exists():
        raise SystemExit(f"no peak table for device kind {device_kind!r} "
                         f"({path.name})")
    return load_json(path)


def out_dir(*parts: str) -> Path:
    """A fixed directory inside the checkout for run outputs (ignored by
    git)."""
    d = REPO.joinpath(".storm_bench", *parts)
    d.mkdir(parents=True, exist_ok=True)
    return d

