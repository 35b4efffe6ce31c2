"""Roofline work counts: hand-computed values, and no dependence on the
gateway's padded slots."""

import numpy as np
import pytest

import _bench_path  # noqa: F401
from storm_bench import cells, layers, work

PARK = cells.load_json(cells.HERE / "configs" / "parkinsons-r2048.json")


def test_query_work_by_hand():
    flops, nbytes = work.query(1000, PARK)
    d, r, p = 24, 2048, 4
    assert flops == 2 * 1000 * d * r * p
    assert nbytes == 4 * 1000 * (d + 1) + 4 * r * p * d + 1000 * r * 4


def test_query_bytes_stop_at_the_whole_bank():
    bank = 1024 * 2048 * 16 * 4
    _, nbytes = work.query(10**6, PARK)
    assert nbytes == 4 * 10**6 * 25 + 4 * 2048 * 4 * 24 + bank


def test_insert_work_by_hand():
    flops, nbytes = work.insert(131072, PARK)
    assert flops == 2 * 131072 * 24 * 2048 * 4
    assert nbytes == 4 * 131072 * 22 + 4 * 2048 * 4 * 24 \
        + 2 * 1024 * 2048 * 16 * 4


def test_counts_ignore_slot_padding():
    padded = dict(PARK, ingest_slots=4096, query_slots=512)
    for fn in (work.query, work.insert):
        assert fn(777, padded) == fn(777, PARK)


def test_roofline_reader_by_hand():
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    ticks = np.asarray([[0.0, 0.01, 0, 131072], [0.1, 0.01, 0, 65536]])
    trace = {"device": {"/device:TPU:0": [
        ["_insert_kernel", 1_000_000, 2_000_000],
        ["_insert_kernel", 5_000_000, 2_000_000],
        ["fusion.1", 8_000_000, 1_000_000]]},
        "spans": [["storm.window", 0, 0]]}
    run = {"ticks": ticks, "config": PARK, "peaks": peaks, "trace": trace,
           "seconds": 1.0, "summary": {"busy_s": 0.005, "window_s": 1.0}}
    least = work.least_seconds(*work.insert(ticks[:, 3], PARK), peaks)
    want = 100 * least.mean() * 2 / 4e-3
    assert abs(layers.roofline(run, "insert") - want) < 1e-9
    assert layers.roofline(run, "query") is None  # no query launches


@pytest.mark.parametrize("name,want", [
    ("tick_start_ms.q", 15.0), ("tick_start_ms.rows", 12.5),
    ("query_slot_fill.q", 100 * 96 / (2 * 1024 * 16))])
def test_tick_readers_by_hand(name, want):
    # ticks: (host start, tick_start seconds, points, rows)
    ticks = np.asarray([[0.0, 0.010, 32, 0], [0.1, 0.020, 64, 128],
                        [0.2, 0.005, 0, 128]])
    run = {"ticks": ticks, "config": PARK}
    assert cells.metric_reader(name)(run) == pytest.approx(want)
