"""The reduction from a profiler trace to busy time, idle gaps, kernel time
and the breakdown, on a small recorded trace and on hand-made events."""

import json

import jax
import jax.numpy as jnp
import pytest

import _bench_path  # noqa: F401
from storm_bench import cells, layers, trace

FIXTURE = cells.HERE / "fixtures" / "read-zipf-trace.json"


def test_busy_and_gaps_by_hand():
    ops = [["a", 10, 10], ["b", 15, 10], ["c", 40, 5], ["d", 90, 20]]
    assert trace.busy_ns(ops, 0, 100) == 15 + 5 + 10
    assert trace.idle_gaps(ops, 0, 100) == [(0, 10), (25, 40), (45, 90)]
    assert trace.kernel_ns(ops, ("a", "c"), 0, 100) == (15.0, 2)
    hlo = [["%sketch_query_banked.1 = f32[16384,1] custom-call(f32[16384,24]"
            " %copy.16)", 5, 10],
           ["%fusion.2 = f32[16384] fusion(f32[16384,1] "
            "%sketch_query_banked.1)", 20, 10]]
    assert trace.kernel_ns(hlo, layers.KERNELS["query"], 0, 100) == (10.0, 1)
    spans = [["storm.tick_start", 20, 30], ["storm.tick_finish", 60, 40]]
    assert trace.label((25, 40), spans) == "storm.tick_start"
    assert trace.label((45, 90), spans) == "storm.tick_finish"
    assert trace.label((0, 10), spans) == "engine idle"


def test_summary_by_hand():
    events = {"device": {"/device:TPU:0": [
        ["_query_kernel", 1_000_000_100, 300_000_000],
        ["fusion.3", 1_400_000_000, 100_000_000],
        ["_query_kernel", 1_600_000_000, 300_000_000]]},
        "spans": [["storm.window", 1_000_000_000, 1000],
                  ["storm.tick_start", 1_300_000_000, 50_000_000]]}
    s = trace.summarize(events, 1.0)
    assert s["busy_s"] == pytest.approx(0.7)
    assert s["device_ops"][0] == ["_query_kernel", pytest.approx(0.6)]
    gaps = dict(s["idle_gaps"])
    assert s["idle_gaps"][0][1] == pytest.approx(0.1)
    assert "storm.tick_start" in gaps
    run = {"summary": s}
    assert layers.idle_share(run) == pytest.approx(30.0)


def test_recorded_trace_reduces():
    """A chip trace of ``parkinsons.read-zipf`` (TPU v5 lite; the first
    0.4 s of the window), kept as the extracted events: the query kernel's
    launches are found by their HLO name and take most of the busy time."""
    events = json.loads(FIXTURE.read_text())
    seconds = events.pop("seconds")
    s = trace.summarize(events, seconds)
    lo, hi = trace.window(events, seconds)
    ops = events["device"]["/device:TPU:0"]
    assert 0 < s["busy_s"] <= s["window_s"] == seconds
    assert s["busy_s"] == pytest.approx(trace.busy_ns(ops, lo, hi) / 1e9)
    busy_ops = sum(v for _, v in s["device_ops"])
    assert busy_ops <= sum(d for _, st, d in ops if lo <= st < hi) / 1e9
    q, n = trace.kernel_ns(ops, layers.KERNELS["query"], lo, hi)
    # a launch that starts in the window counts whole; one may run past it
    assert n > 0 and 0.5 * s["busy_s"] < q / 1e9 <= s["busy_s"] + 0.05
    gaps = sum(g for _, g in s["idle_gaps"])
    assert gaps <= seconds - s["busy_s"] + 1e-9


def test_extract_reads_harness_spans_from_a_profiler_trace(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("storm.window"):
        pass
    with jax.profiler.TraceAnnotation("storm.tick_start"):
        jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    events = trace.extract(tmp_path)
    names = [n for n, _, _ in events["spans"]]
    assert names.count("storm.window") == 1
    assert "storm.tick_start" in names
