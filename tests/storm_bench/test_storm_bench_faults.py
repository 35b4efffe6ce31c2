"""``correct`` comes out false where the timed path is broken underneath,
and for the control: the reference one precision step down (three bfloat16
passes for the float32 projections) in the program's place."""

import numpy as np
import pytest

import _bench_path  # noqa: F401
from repro.serve.storm_gateway import StormGateway
from storm_bench import check, payloads, run
from storm_bench.reference import Reference

SEED = 3_000_000_029


def _stale_state(monkeypatch):
    """An ingest step that returns the counters it was given."""
    start = StormGateway.tick_start

    def tick_start(self):
        counts, n = self._counts, self._n
        tick = start(self)
        self._counts, self._n = counts, n
        return tick
    monkeypatch.setattr(StormGateway, "tick_start", tick_start)


def _half_batch(monkeypatch):
    """Half of each tick's ingest slots left out."""
    pack = StormGateway._pack_ingest

    def pack_ingest(self):
        zbuf, zmask, taken, done = pack(self)
        zmask[:, zmask.shape[1] // 2:] = 0.0
        return zbuf, zmask, taken, done
    monkeypatch.setattr(StormGateway, "_pack_ingest", pack_ingest)


def _row_altered(monkeypatch):
    """Rows altered where the gateway packs them."""
    pack = StormGateway._pack_ingest

    def pack_ingest(self):
        zbuf, zmask, taken, done = pack(self)
        return zbuf * np.float32(0.9), zmask, taken, done
    monkeypatch.setattr(StormGateway, "_pack_ingest", pack_ingest)


def _answer_altered(monkeypatch):
    """Every answer off by one part in ten thousand where it is produced."""
    finish = StormGateway.tick_finish

    def tick_finish(self, inflight):
        report = finish(self, inflight)
        for r in report.results:
            r.losses = r.losses * np.float32(1.0001)
        return report
    monkeypatch.setattr(StormGateway, "tick_finish", tick_finish)


@pytest.mark.parametrize("fault,cell", [
    (_stale_state, "tiny.read"), (_stale_state, "tiny.load"),
    (_half_batch, "tiny.load"), (_row_altered, "tiny.load"),
    (_answer_altered, "tiny.read"), (_answer_altered, "tiny.flood")])
def test_fault_makes_the_run_incorrect(fault, cell, monkeypatch):
    fault(monkeypatch)
    result = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       "1.0", "--trace", "0", "--rehearse"])
    assert not result["correct"], result["checks"]


def test_control_fails_at_a_size_a_test_can_hold():
    """At the parkinsons widths (R=2048, p=4, 22-wide rows): tenant 0 holds
    4,096 rows, tenant 1 holds 8 rows and is asked 4,096 query points.
    The control's counters and answers both exceed their limits, where the
    reference itself, in the program's place with its own float32 pad,
    passes."""
    rng = payloads.rng_of(SEED, 0)
    w = rng.normal(size=(2048, 4, 24)).astype(np.float32)
    rows = payloads.row_pool(rng, 21, 8, 0.4, 50.0, blocks=512)
    thetas = payloads.theta_pool(rng, 22, 8, blocks=512)
    n = 512 + 1 + 512
    log = {"kind": np.zeros(n, int), "failed": np.zeros(n, bool),
           "done": np.ones(n), "tenant": np.zeros(n, int),
           "tick": np.ones(n, int), "block": np.arange(n) % 512,
           "sent": np.zeros(n)}
    log["tenant"][512:] = 1
    log["kind"][513:] = 1
    log["block"][513:] = np.arange(512)
    log["tick"][513:] = 2
    rids = np.arange(513, n)
    starts = np.asarray([0.0, 1.0])
    sent = np.asarray([512 * 8, 8])

    def numbers(precision):
        kw = dict(log=log, rows=rows, thetas=thetas, counted=[0],
                  hash_rows=np.arange(0, 2048, 32), rids=rids,
                  tick_starts=starts)
        expected = check.replay(Reference(w), **kw)
        got = check.replay(Reference(w, precision), paths=False, **kw)
        return check.compare(expected, check.control_state(*got, sent),
                             sent, 0, log["tenant"])

    sound = numbers("highest")
    assert check.correct(sound), sound
    control = numbers("high")
    assert control["n_off"]["value"] == control["mass_off"]["value"] == 0
    assert control["counts_moved_share"]["value"] \
        > control["counts_moved_share"]["limit"]
    assert control["query_off_share"]["value"] \
        > control["query_off_share"]["limit"]
    assert not check.correct(control)
