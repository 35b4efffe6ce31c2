"""Every cell's files are found by name from BENCHMARK.json, and the file
keeps to the benchmark's contract."""

import json
import re

import pytest

import _bench_path  # noqa: F401
from storm_bench import cells

BENCH = cells.load_json(cells.REPO / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_are_found_by_name(cell):
    c = cells.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    assert cells.generator(c.traffic["kind"]).Driver is not None
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(cells.metric_reader(m["name"]))
        assert m["moves"] in names  # the metric it moves is reported here


def test_rehearsal_cells_are_found_by_name():
    for w in cells.load_json(cells.HERE / "rehearsal.json")["workloads"]:
        c = cells.load_cell(w["name"], rehearsal=True)
        assert c.config["tenants"] <= 8


def test_benchmark_file_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in BENCH["configs"]:
        assert c["file"].startswith("storm_bench/")
        assert json.loads((cells.REPO / c["file"]).read_text())["name"] \
            == c["name"]
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x) <= 200 for x in layers)
    # A cell of four chips runs its deployment over all four (the bank
    # split over a mesh of exactly the cell's devices: the ``*-4dev``
    # rehearsals in test_storm_bench_deployments.py).
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_peaks_are_keyed_by_device_kind():
    p = cells.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        cells.peaks("TPU v99")
