"""The served path as the window drives it: one ``StormGateway``, its
double-buffered engine loop, and a log of every request.

The loop is the wire server's ``_engine_loop`` (``serve/wire.py``): up to two
ticks in flight, ``tick_start`` under the submit lock, ``tick_finish``
outside it. Two things are added. Each request's completion is stamped on
the host clock as the loop routes it out. And an ingest acknowledgement waits
until the tick that carried its rows has run on the device
(``tick_finish`` reads nothing back for an ingest-only tick), so an
acknowledged row is in the counters and the ingest rate is the device's,
not the dispatch queue's.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import jax
import numpy as np

from repro.serve.storm_gateway import IngestRequest, QueryRequest

INGEST, QUERY = 0, 1
SETUP, WINDOW, AFTER = 0, 1, 2
DEPTH = 2           # ticks in flight, as the wire server keeps them
IDLE_S = 0.0002     # the wire server's idle sleep

clock = time.perf_counter
annotate = jax.profiler.TraceAnnotation


class Server:
    """A gateway, its engine loop, and the request log.

    Per request (index = rid): kind, tenant, pool block, phase, due time,
    submit time, completion time (NaN until done), the tick that completed it, and
    whether it failed. Per dispatched tick: host start time, ``tick_start``
    seconds, real points and rows carried.
    """

    def __init__(self, gw, rows: np.ndarray, thetas: np.ndarray):
        self.gw = gw
        self.rows, self.thetas = rows, thetas
        self.lock = threading.Lock()
        self.kind, self.tenant, self.block, self.phase = [], [], [], []
        self.due, self.sent, self.done = [], [], []
        self.tick, self.failed = [], []
        self.answers = {}  # query rid -> served losses
        self.ticks = []  # (host start, tick_start seconds, points, rows)

    def submit(self, kind: int, tenant: int, block: int, due: float,
               phase: int) -> int:
        """Log and submit one request; the caller holds ``self.lock``."""
        rid = len(self.kind)
        for col, v in ((self.kind, kind), (self.tenant, tenant),
                       (self.block, block), (self.phase, phase),
                       (self.due, due), (self.sent, clock()),
                       (self.done, np.nan),
                       (self.tick, -1), (self.failed, False)):
            col.append(v)
        try:
            if kind == INGEST:
                self.gw.submit(IngestRequest(rid, tenant, self.rows[block]))
            else:
                self.gw.submit(QueryRequest(rid, tenant, self.thetas[block]))
        except Exception:  # noqa: BLE001 — a refused request counts as failed
            self.failed[rid] = True
            self.done[rid] = clock()
        return rid

    def run(self, closing, on_done=None) -> None:
        """Serve until ``closing()`` holds and nothing is pending or in
        flight. ``on_done(rids, now)`` sees each tick's completions under
        the submit lock; it only hands them on, since clients run on
        threads of their own."""
        gw = self.gw
        inflight = deque()
        closed = False
        while True:
            closed = closed or closing()
            with self.lock:
                while gw.pending and len(inflight) < DEPTH:
                    t0 = clock()
                    with annotate("storm.tick_start"):
                        tick = gw.tick_start()
                    self.ticks.append((t0, clock() - t0, tick.points,
                                       tick.rows))
                    inflight.append((tick, gw.bank.n))
            if not inflight:
                if closed and not gw.pending:
                    return
                time.sleep(IDLE_S)
                continue
            tick, n_after = inflight.popleft()
            with annotate("storm.tick_finish"):
                report = gw.tick_finish(tick)
                if report.ingest_done:
                    n_after.block_until_ready()
            now = clock()
            with annotate("storm.route"):
                rids = [r.rid for r in report.results]
                for r in report.results:
                    self.answers[r.rid] = r.losses
                    if r.status != "ok":
                        self.failed[r.rid] = True
                rids += [r.rid for r in report.ingest_done]
                for rid in rids:
                    self.done[rid] = now
                    self.tick[rid] = tick.tick
                if on_done is not None:
                    with self.lock:
                        on_done(rids, now)

    def table(self) -> dict:
        """The request log as arrays."""
        return {k: np.asarray(getattr(self, k)) for k in
                ("kind", "tenant", "block", "phase", "due", "sent", "done",
                 "tick", "failed")}
