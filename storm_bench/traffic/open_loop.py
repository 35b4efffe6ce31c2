"""Open loop: independent users send on a schedule, whatever the server does.

Parameters (``traffic/<name>.json``): ``rate_per_s`` requests per second,
``ingest_share`` of them ingest requests of ``ingest_rows`` rows (the rest
query requests of ``query_points`` thetas), tenants drawn under a Zipf law
of exponent ``zipf_s`` (0 is uniform), and ``fill_rows`` rows that every
tenant holds before the window.

The schedule covers the window and a tail at the same rate, which keeps the
load on until every request due in the window has completed (at most
``FOLLOW_S`` past the close). A request is stamped with its due time, so a
late generator shows in the latency it causes.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from storm_bench import payloads
from storm_bench.serve import (AFTER, INGEST, QUERY, WINDOW, annotate,
                               clock)

FOLLOW_S = 60.0


class Driver:
    def __init__(self, spec: dict, config: dict, seed: int, seconds: float,
                 rate: float = None):
        self.rate = float(rate or spec["rate_per_s"])
        self.seconds = seconds
        rng = payloads.rng_of(seed, 3)
        parts = []
        for n in (round(self.rate * seconds), round(self.rate * FOLLOW_S)):
            due = payloads.arrivals(rng, n, self.rate)
            kind = np.full(n, QUERY, np.int8)
            kind[:round(n * spec["ingest_share"])] = INGEST
            rng.shuffle(kind)
            tenant = payloads.tenant_draws(rng, n, config["tenants"],
                                           spec["zipf_s"])
            parts.append((due, kind, tenant))
        (d0, k0, t0), (d1, k1, t1) = parts
        self.n_window = len(d0)
        self.due = np.concatenate([d0, d1 + (d0[-1] if len(d0) else 0.0)])
        self.kind = np.concatenate([k0, k1])
        self.tenant = np.concatenate([t0, t1])
        self.block = rng.integers(0, payloads.POOL_BLOCKS, len(self.due))
        self.programs = ({"ingest", "query", "full"}
                         if 0 < spec["ingest_share"] < 1
                         else {"ingest"} if spec["ingest_share"] else
                         {"query"})
        self.lag = np.full(len(self.due), np.nan)
        self._window_left = self.n_window
        self._stop = threading.Event()
        self._thread = None

    def start(self, server, t0: float) -> None:
        self.server, self.t0 = server, t0
        self.deadline = t0 + self.seconds + FOLLOW_S
        self._thread = threading.Thread(target=self._send, daemon=True)
        self._thread.start()

    def _send(self) -> None:
        srv, i, n = self.server, 0, len(self.due)
        while i < n and not self._stop.is_set():
            now = clock() - self.t0
            if self.due[i] > now:
                time.sleep(min(self.due[i] - now, 0.001))
                continue
            with srv.lock, annotate("storm.submit"):
                while i < n and self.due[i] <= now:
                    rid = srv.submit(int(self.kind[i]), int(self.tenant[i]),
                                     int(self.block[i]),
                                     self.t0 + self.due[i],
                                     WINDOW if i < self.n_window else AFTER)
                    self.lag[i] = clock() - self.t0 - self.due[i]
                    if srv.failed[rid] and i < self.n_window:
                        self._window_left -= 1
                    i += 1

    def on_done(self, rids, now) -> None:
        """Counts the window's completions (called under the submit lock,
        as ``_send`` counts refused submits)."""
        phase = self.server.phase
        self._window_left -= sum(phase[r] == WINDOW for r in rids)

    def closing(self) -> bool:
        done = (self._window_left <= 0 and self._sent_window()) \
            or clock() > self.deadline
        if done:
            self._stop.set()
        return done

    def _sent_window(self) -> bool:
        return np.isfinite(self.lag[self.n_window - 1]) \
            if self.n_window else True

    def join(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    def lag_summary(self) -> dict:
        lag = self.lag[:self.n_window]
        lag = lag[np.isfinite(lag)] * 1e3
        if not len(lag):
            return {}
        return {"lag_p50_ms": float(np.percentile(lag, 50)),
                "lag_p99_ms": float(np.percentile(lag, 99)),
                "lag_max_ms": float(lag.max())}
