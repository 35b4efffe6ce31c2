"""A configuration brings its deployment: ``run.py`` builds and checks
every cell through ``deployments/<name>.py``, which honours the cell's
``chips`` and the configuration's ``paired``."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import _bench_path  # noqa: F401
from _bench_path import ROOT
from storm_bench import cells, run
from storm_bench.serve import INGEST, QUERY, SETUP, WINDOW

SEED = 3_000_000_037


@pytest.mark.parametrize("path", sorted((cells.HERE / "configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_every_configuration_names_a_deployment_module(path):
    name = cells.load_json(path)["deployment"]
    assert (cells.HERE / "deployments" / f"{name}.py").is_file()
    assert callable(cells.deployment(name).build)


def _refused(monkeypatch, capsys, **config):
    """Runs a rehearsal cell whose configuration is changed by ``config``
    (a value of None drops the key) and returns the refusal."""
    cell = cells.load_cell("tiny.load", rehearsal=True)
    cfg = {k: v for k, v in dict(cell.config, **config).items()
           if v is not None}
    monkeypatch.setattr(cells, "load_cell", lambda *a, **k:
                        dataclasses.replace(cell, config=cfg))
    with pytest.raises(SystemExit) as refusal:
        run.main(["--workload", "tiny.load", "--seed", str(SEED),
                  "--seconds", "1", "--trace", "0", "--rehearse"])
    assert capsys.readouterr().out == ""
    assert isinstance(refusal.value.code, str)  # a message: exit status 1
    return refusal.value.code


def test_a_single_sided_configuration_is_refused(monkeypatch, capsys):
    assert "paired only" in _refused(monkeypatch, capsys, paired=False)


@pytest.mark.parametrize("name", ["classification", None])
def test_an_unknown_or_missing_deployment_is_refused(name, monkeypatch,
                                                     capsys):
    message = _refused(monkeypatch, capsys, deployment=name)
    assert "no deployment module" in message and "regression" in message


def test_one_chip_runs_the_meshless_gateway():
    import jax

    cell = cells.load_cell("tiny.load", rehearsal=True)
    built = cells.deployment("regression").build(cell, jax.devices()[:1],
                                                 SEED, True)
    assert built.server.gw.mesh is None
    assert built.layout() == {"mesh": None, "bank_devices": 1,
                              "mode": "interpret"}
    assert built.carried == {INGEST: 4, QUERY: 0}


@pytest.mark.parametrize("cell", ["tiny.load-4dev", "tiny.flood-4dev"])
def test_four_chips_split_the_bank_over_all_four(cell):
    """On four CPU devices: the gateway's bank is sharded over each of
    them, and the run is correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "storm_bench/run.py", "--workload", cell, "--seed",
         str(SEED), "--seconds", "1.5", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"], result["checks"]
    assert result["device"]["count"] == 4 and result["attempted"] > 0
    info = next(json.loads(line[len("run: "):])
                for line in proc.stderr.splitlines()
                if line.startswith("run: "))
    assert info["layout"]["mesh"] == {"bank": 4}
    assert info["layout"]["bank_devices"] == 4


def _log():
    """Nine requests around a window of [10, 12) s: rid 0 in set-up; queries
    1-5 (answered at 10.5 and 11.0, at 12.5 after the close, never, and
    failed); ingests 6-8 (answered at 10.4, 11.99, and 12.1)."""
    nan = np.nan
    return {
        "kind": np.asarray([INGEST] + [QUERY] * 5 + [INGEST] * 3),
        "phase": np.asarray([SETUP] + [WINDOW] * 8),
        "due": np.asarray([4.0, 10.0, 10.2, 11.0, 11.5, 11.6, 10.1, 10.3,
                           11.9]),
        "done": np.asarray([5.0, 10.5, 11.0, 12.5, nan, 11.9, 10.4, 11.99,
                            12.1]),
        "failed": np.asarray([False] * 5 + [True] + [False] * 3),
    }


@pytest.mark.parametrize("open_loop,expected", [
    (False, {"query_points_per_s": 2 * 8 / 2.0,
             "ingest_rows_per_s": 2 * 64 / 2.0}),
    (True, {"query_points_per_s": 3 * 8 / 2.0,
            "ingest_rows_per_s": 3 * 64 / 2.0})])
def test_end_to_end_rates_count_what_each_request_carried(open_loop,
                                                          expected):
    got = run.e2e_metrics(_log(), 10.0, 2.0, 7.5, {INGEST: 64, QUERY: 8},
                          open_loop)
    assert {k: got[k] for k in expected} == expected
    assert got["setup_s"] == 7.5
    lat = run.e2e_metrics({k: v[:4] for k, v in _log().items()}, 10.0, 2.0,
                          7.5, {INGEST: 64, QUERY: 8}, open_loop)
    assert lat["query_p99_ms"] == pytest.approx(
        np.percentile([0.5, 0.8, 1.5], 99) * 1e3)
