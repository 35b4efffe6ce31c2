"""The gateway's own spans (DESIGN.md §11.5): ``storm.gw.*`` spans at tick
granularity inside ``tick_start`` and ``tick_finish``, one
``storm.gw.trace`` span per trace of a tick program, a ``storm.gc`` span over
collections of the oldest generation, and stable names for the tick
programs. Each trace is recorded with ``jax.profiler`` into a temporary
directory and read back with ``storm_bench.trace.extract``; the spans'
stats are read from the same ``.xplane.pb``."""

import gc
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation
from jax.sharding import Mesh

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from repro.core import lsh  # noqa: E402
from repro.serve import storm_gateway  # noqa: E402
from repro.serve.storm_gateway import (IngestRequest,  # noqa: E402
                                       QueryRequest, StormGateway)
from storm_bench import trace  # noqa: E402

S, D = 4, 5
STAGES = ("storm.gw.pack_ingest", "storm.gw.pack_queries",
          "storm.gw.flatten", "storm.gw.launch")


@pytest.fixture(scope="module")
def params():
    return lsh.init_srp(jax.random.PRNGKey(0), 64, 3, D + 2)


def _gateway(params, **kw):
    return StormGateway(params, S, query_slots=4, ingest_slots=8, **kw)


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, D)) * 0.3).astype(np.float32)


def _thetas(n, seed=1):
    return np.random.default_rng(seed).normal(size=(n, D)).astype(np.float32)


def _submit_mixed(gw, rid=0):
    gw.submit(IngestRequest(rid=rid, tenant=0, z=_rows(5)))
    gw.submit(IngestRequest(rid=rid + 1, tenant=2, z=_rows(3, 2)))
    gw.submit(QueryRequest(rid=rid + 2, tenant=1, thetas=_thetas(3)))


def _profiled(tmp_path, fn):
    """``fn()`` under a profiler session; returns its result, the spans
    sorted by start, and ``{name: [stats, ...]}`` of the ``storm.`` spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    spans = sorted(trace.extract(tmp_path)["spans"], key=lambda e: e[1])
    xplane = max(tmp_path.rglob("*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
    stats = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("storm."):
                    stats.setdefault(e.name, []).append(
                        {k: v for k, v in e.stats})
    return out, spans, stats


def _names(spans, prefix="storm.gw."):
    return [n for n, _, _ in spans if n.startswith(prefix)]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def _tick_start(gw):
    with TraceAnnotation("storm.tick_start"):
        return gw.tick_start()


def test_programs_are_named_after_their_tick(params):
    gw = _gateway(params)
    assert (gw._tick_full.__name__, gw._tick_ingest.__name__,
            gw._tick_query.__name__) == ("tick_full", "tick_ingest",
                                         "tick_query")


def test_mixed_tick_start_emits_its_stages_in_order(params, tmp_path):
    gw = _gateway(params)
    _submit_mixed(gw)
    gw.tick()  # compile outside the profile
    _submit_mixed(gw, rid=3)
    inflight, spans, stats = _profiled(tmp_path, lambda: _tick_start(gw))
    gw.tick_finish(inflight)
    assert [n for n in _names(spans) if n in STAGES] == list(STAGES)
    outer = next(e for e in spans if e[0] == "storm.tick_start")
    assert all(_inside(e, outer) for e in spans if e[0] in STAGES)
    (ingest,) = stats["storm.gw.pack_ingest"]
    assert ingest["rows"] == 8 and ingest["requests"] == 2
    assert ingest["oldest_wait_ms"] >= 0
    (query,) = stats["storm.gw.pack_queries"]
    assert query["points"] == 3 and query["requests"] == 1
    (flat,) = stats["storm.gw.flatten"]
    assert flat["h2d_bytes"] == 4 * S * (8 * (D + 1) + 4 * (D + 1))
    assert stats["storm.gw.launch"] == [{"program": "tick_full"}]


def test_query_tick_finish_reads_back_then_scatters(params, tmp_path):
    gw = _gateway(params)
    gw.submit(QueryRequest(rid=0, tenant=1, thetas=_thetas(3)))
    inflight = gw.tick_start()
    report, spans, stats = _profiled(tmp_path,
                                     lambda: gw.tick_finish(inflight))
    assert _names(spans) == ["storm.gw.readback", "storm.gw.scatter"]
    assert stats["storm.gw.readback"] == [{"d2h_bytes": 4 * S * 4}]
    assert [r.rid for r in report.results] == [0]
    # the finished tick holds no request bookkeeping past its finish
    assert inflight.placements == [] and inflight.completes == []


def test_ingest_only_tick_packs_no_queries(params, tmp_path):
    gw = _gateway(params)
    gw.submit(IngestRequest(rid=0, tenant=0, z=_rows(5)))
    gw.tick()
    gw.submit(IngestRequest(rid=1, tenant=3, z=_rows(2)))
    _, spans, stats = _profiled(tmp_path, gw.tick)
    names = _names(spans)
    assert "storm.gw.pack_queries" not in names
    assert "storm.gw.readback" not in names
    assert "storm.gw.scatter" not in names
    assert "storm.gw.pack_ingest" in names
    assert stats["storm.gw.launch"] == [{"program": "tick_ingest"}]


def test_a_trace_span_per_program_trace_and_none_on_a_repeat(params,
                                                             tmp_path):
    gw = _gateway(params)

    def two_query_ticks():
        for rid in range(2):
            gw.submit(QueryRequest(rid=rid, tenant=0, thetas=_thetas(2)))
            gw.tick()

    _, spans, stats = _profiled(tmp_path, two_query_ticks)
    assert _names(spans).count("storm.gw.trace") == 1
    assert stats["storm.gw.trace"] == [{"program": "tick_query",
                                        "query_path": "ref"}]
    assert _names(spans).count("storm.gw.launch") == 2
    first_launch = next(e for e in spans if e[0] == "storm.gw.launch")
    traced = next(e for e in spans if e[0] == "storm.gw.trace")
    assert _inside(traced, first_launch)


def test_mesh_path_ships_only_the_halves_it_runs(params, tmp_path):
    mesh = Mesh(np.array(jax.devices()[:1]), ("bank",))
    gw = _gateway(params, mesh=mesh, mode="ref")
    gw.submit(QueryRequest(rid=0, tenant=2, thetas=_thetas(3)))
    gw.tick()
    gw.submit(QueryRequest(rid=1, tenant=2, thetas=_thetas(3)))
    report, _, stats = _profiled(tmp_path, gw.tick)
    assert stats["storm.gw.flatten"] == [{"h2d_bytes": 4 * S * 4 * (D + 1)}]
    assert stats["storm.gw.launch"] == [{"program": "tick_query"}]
    assert gw._tick_query.__name__ == "tick_query"
    assert report.results[0].losses.shape == (3,)


def _serve(gw):
    """A script of mixed, split and query-only ticks; every report's
    answers, and the counters after it."""
    out = []
    for r in range(4):
        gw.submit(IngestRequest(rid=3 * r, tenant=r % S, z=_rows(11, r)))
        gw.submit(QueryRequest(rid=3 * r + 1, tenant=(r + 1) % S,
                               thetas=_thetas(5, r)))
        gw.submit(QueryRequest(rid=3 * r + 2, tenant=r % S,
                               thetas=_thetas(0, r)))
        report = gw.tick()
        out.append(([(q.rid, q.losses.tobytes()) for q in report.results],
                    np.asarray(gw.bank.counts).tobytes(),
                    np.asarray(gw.bank.n).tobytes()))
    while gw.pending:
        gw.tick()
    out.append((gw.rows_ingested, gw.points_served, gw.ticks,
                gw.trace_count))
    return out


def test_answers_and_counters_are_the_same_under_a_profile(params, tmp_path):
    plain = _serve(_gateway(params))
    traced, spans, _ = _profiled(tmp_path, lambda: _serve(_gateway(params)))
    assert "storm.gw.launch" in _names(spans)
    assert traced == plain


def test_gc_span_covers_oldest_generation_collections_only(params, tmp_path):
    _gateway(params)
    _gateway(params)
    assert gc.callbacks.count(storm_gateway._GC_SPAN) == 1

    def collect():
        gc.collect(0)
        gc.collect(1)
        gc.collect()

    _, spans, _ = _profiled(tmp_path, collect)
    assert _names(spans, "storm.gc") == ["storm.gc"]
