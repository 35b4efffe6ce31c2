"""CPU rehearsals of the harness: the same command at a test-only tiny
configuration, Pallas kernels in interpret mode, end to end; and a run
without a TPU that must fail and print nothing."""

import json
import os
import subprocess
import sys

import pytest

import _bench_path  # noqa: F401
from _bench_path import ROOT
from storm_bench import run

SEED = 3_000_000_023


@pytest.mark.parametrize("cell,trace", [("tiny.read", 0), ("tiny.read", 1),
                                        ("tiny.flood", 0), ("tiny.flood", 1),
                                        ("tiny.load", 0), ("tiny.load", 1)])
def test_rehearsal_runs_and_is_correct(cell, trace, capsys):
    result = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       "1.5", "--trace", str(trace), "--rehearse"])
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "metrics" not in result  # CPU numbers never sit under it
    names = set(result["cpu_metrics"])
    if trace:
        assert names and all("." in n for n in names)
    else:
        assert "setup_s" in names
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert list(json.loads(last))[-1] == "checks"


def test_without_a_tpu_the_run_fails_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "storm_bench/run.py", "--workload",
         "airfoil.load-uniform", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
