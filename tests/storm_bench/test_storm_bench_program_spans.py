"""The readers of the program's own ``storm.gw.*`` spans
(``storm_bench/program_spans.py`` and the metrics that use it): on
hand-made events, and in CPU rehearsals with ``--trace 1``."""

import pytest

import _bench_path  # noqa: F401
from storm_bench import cells, run

SEED = 3_000_000_031
MS = 1_000_000  # nanoseconds


def _run(*spans):
    """A traced run whose window starts at 1 s and lasts 1 s."""
    return {"seconds": 1.0,
            "trace": {"device": {},
                      "spans": [["storm.window", 1000 * MS, 1000],
                                *[list(s) for s in spans]]}}


def _read(name, run_):
    return cells.metric_reader(name)(run_)


EVENTS = _run(
    # before the window: left out
    ("storm.gw.pack_ingest", 900 * MS, 50 * MS),
    ("storm.gw.launch", 950 * MS, 9 * MS),
    # two ingest ticks in the window
    ("storm.tick_start", 1100 * MS, 100 * MS),
    ("storm.gw.pack_ingest", 1101 * MS, 80 * MS),
    ("storm.gw.flatten", 1181 * MS, 6 * MS),
    ("storm.gw.launch", 1187 * MS, 10 * MS),
    ("storm.tick_start", 1300 * MS, 100 * MS),
    ("storm.gw.pack_ingest", 1301 * MS, 60 * MS),
    ("storm.gw.flatten", 1361 * MS, 4 * MS),
    ("storm.gw.launch", 1365 * MS, 20 * MS),
    # queries: packed twice, scattered three times
    ("storm.gw.pack_queries", 1500 * MS, 7 * MS),
    ("storm.gw.pack_queries", 1600 * MS, 9 * MS),
    ("storm.gw.readback", 1700 * MS, 40 * MS),
    ("storm.gw.scatter", 1741 * MS, 1 * MS),
    ("storm.gw.scatter", 1800 * MS, 2 * MS),
    ("storm.gw.scatter", 1900 * MS, 3 * MS),
    # after the window: left out
    ("storm.gw.scatter", 2100 * MS, 50 * MS),
)


@pytest.mark.parametrize("name,want", [
    ("pack_ms.rows", (80 + 60) / 2),
    ("pack_ms.q", (7 + 9) / 2),
    ("dispatch_ms.rows", (6 + 10 + 4 + 20) / 2),
    ("dispatch_ms.q", (6 + 10 + 4 + 20) / 2),
    ("finish_ms.q", (1 + 2 + 3) / 3),
])
def test_readers_by_hand(name, want):
    assert _read(name, EVENTS) == pytest.approx(want)


@pytest.mark.parametrize("name", ["pack_ms.rows", "pack_ms.q",
                                  "dispatch_ms.rows", "dispatch_ms.q",
                                  "finish_ms.q"])
def test_readers_give_none_without_the_program_spans(name):
    """A program that records no ``storm.gw.*`` span, as before the spans
    existed: only the harness's own, and program spans outside the
    window."""
    harness_only = _run(("storm.tick_start", 1100 * MS, 100 * MS),
                        ("storm.tick_finish", 1200 * MS, 50 * MS),
                        ("storm.gw.pack_ingest", 2500 * MS, 50 * MS),
                        ("storm.gw.pack_queries", 2500 * MS, 50 * MS),
                        ("storm.gw.launch", 2600 * MS, 5 * MS),
                        ("storm.gw.scatter", 2700 * MS, 5 * MS))
    assert _read(name, harness_only) is None


def test_dispatch_counts_ticks_by_their_launches():
    """A flatten without a launch in the window (a mesh tick whose
    ``device_put`` calls start just before its end) adds its time but no
    tick."""
    events = _run(("storm.gw.flatten", 1100 * MS, 2 * MS),
                  ("storm.gw.launch", 1102 * MS, 4 * MS),
                  ("storm.gw.flatten", 1999 * MS, 3 * MS))
    assert _read("dispatch_ms.q", events) == pytest.approx(2 + 4 + 3)
    assert _read("dispatch_ms.q", _run(("storm.gw.flatten", 1100 * MS,
                                        2 * MS))) is None


@pytest.mark.parametrize("cell,names", [
    ("tiny.load", {"pack_ms.rows", "dispatch_ms.rows"}),
    ("tiny.flood", {"pack_ms.q", "dispatch_ms.q", "finish_ms.q"}),
])
def test_rehearsal_reports_the_span_metrics(cell, names):
    result = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       "1.5", "--trace", "1", "--rehearse"])
    assert result["correct"], result["checks"]
    got = result["cpu_metrics"]
    assert names <= set(got)
    assert all(got[n]["value"] > 0 and got[n]["unit"] == "ms" for n in names)
