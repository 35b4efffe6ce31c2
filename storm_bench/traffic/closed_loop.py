"""Closed loop: each tenant has one client that keeps ``in_flight``
requests outstanding, and sends the next the moment one is answered. The
gateway stays saturated while the backlog stays bounded, so the rate it
answers is its capacity.

Parameters (``traffic/<name>.json``): ``in_flight``, ``ingest_rows``,
``fill_rows``, and ``query_points``: where it is given, the clients send
query requests of that many thetas, else ingest requests of
``ingest_rows`` rows. The k-th request of tenant t takes pool block
``(a_t + k * b_t) mod POOL_BLOCKS`` with ``a``, ``b`` drawn from the seed, so
the payloads do not depend on timing. The clients resubmit from a thread of
their own, as the open loop's users submit from theirs, so the engine loop
only hands completions on. No request is sent after the window closes; the
ones in flight complete before the run ends.
"""

from __future__ import annotations

import threading
from collections import deque

import numpy as np

from storm_bench import payloads
from storm_bench.serve import INGEST, QUERY, WINDOW, annotate, clock

WAKE_S = 0.01  # the longest the client thread sleeps between checks


class Driver:
    def __init__(self, spec: dict, config: dict, seed: int, seconds: float,
                 rate: float = None):
        if rate is not None:
            raise SystemExit("a closed loop takes no rate")
        self.kind = QUERY if "query_points" in spec else INGEST
        self.programs = {"query" if self.kind == QUERY else "ingest"}
        self.tenants = config["tenants"]
        self.in_flight = spec["in_flight"]
        self.seconds = seconds
        rng = payloads.rng_of(seed, 3)
        self.a = rng.integers(0, payloads.POOL_BLOCKS, self.tenants)
        self.b = 2 * rng.integers(0, payloads.POOL_BLOCKS // 2,
                                  self.tenants) + 1
        self.sent = np.zeros(self.tenants, np.int64)
        self._done = deque()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread = None

    def _send(self, tenant: int, now: float, phase: int) -> None:
        k = self.sent[tenant]
        self.sent[tenant] += 1
        block = (self.a[tenant] + k * self.b[tenant]) % payloads.POOL_BLOCKS
        self.server.submit(self.kind, tenant, int(block), now, phase)

    def start(self, server, t0: float) -> None:
        self.server, self.t0 = server, t0
        self.close_at = t0 + self.seconds
        with server.lock:
            for _ in range(self.in_flight):
                for t in range(self.tenants):
                    self._send(t, t0, WINDOW)
        self._thread = threading.Thread(target=self._resend, daemon=True)
        self._thread.start()

    def on_done(self, rids, now) -> None:
        """Hands a tick's acknowledgements to the client thread."""
        if now < self.close_at:
            self._done.append(rids)
            self._wake.set()

    def _resend(self) -> None:
        srv = self.server
        while not self._stop.is_set():
            self._wake.wait(WAKE_S)
            self._wake.clear()
            batch = []
            while self._done:
                batch += self._done.popleft()
            if not batch:
                continue
            with srv.lock, annotate("storm.submit"):
                # read under the lock: once the engine loop has seen the
                # window close, nothing more is sent
                now = clock()
                if now >= self.close_at:
                    continue
                for rid in batch:
                    self._send(srv.tenant[rid], now, WINDOW)

    def closing(self) -> bool:
        return clock() >= self.close_at

    def join(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    def lag_summary(self) -> dict:
        return {}
