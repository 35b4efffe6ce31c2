"""STORM sketch-serving launcher: micro-batched gateway over a SketchBank.

Two modes:

* **synthetic drive** (default) — generates mixed per-tenant read/write
  traffic and pumps it through the fixed-tick gateway in-process, either
  synchronously (the PR-5 loop) or double-buffered (``--pipelined``: pack
  tick t+1 on the host while tick t runs on device, DESIGN.md §11).

      PYTHONPATH=src python -m repro.launch.storm_serve --tenants 8 --ticks 32

* **wire front-end** (``--listen HOST:PORT``) — serves the framed
  JSON-or-npz protocol (``serve.wire``) so real clients can submit
  ``IngestRequest``/``QueryRequest`` over a socket; the engine thread runs
  the double-buffered tick loop and admission control turns queue overflow
  into explicit backpressure errors.

      PYTHONPATH=src python -m repro.launch.storm_serve --tenants 8 \\
          --listen 127.0.0.1:7077 --max-pending-rows 4096
"""

import argparse
import itertools
import time
from typing import Iterator, List, Union

import jax
import numpy as np

from repro.core import lsh
from repro.launch import compile_cache
from repro.serve.storm_gateway import (
    FitRequest, IngestRequest, QueryRequest, StormGateway,
)


def synth_traffic(
    rng: np.random.Generator,
    rids: Iterator[int],
    tenants: int,
    dim: int,
    ingest_rate: int,
    query_rate: int,
) -> List[Union[IngestRequest, QueryRequest]]:
    """One round of mixed per-tenant traffic with collision-free rids.

    ``rids`` is a single monotonic counter shared by BOTH request classes
    (``itertools.count()``): request ids are handles that route results
    back to callers, so they must be unique across every request the
    gateway ever sees. (The old scheme — ``tick*1000 + t`` for ingest,
    ``tick*1000 + 500 + t`` for queries — collided whenever
    ``tenants >= 500`` and aliased across ticks beyond 1000 tenants;
    pinned by ``tests/test_serve_wire.py``.)
    """
    reqs: List[Union[IngestRequest, QueryRequest]] = []
    for t in range(tenants):
        n_rows = int(rng.poisson(ingest_rate))
        if n_rows:
            z = rng.normal(size=(n_rows, dim)).astype(np.float32)
            z *= 0.4 / np.sqrt(dim)
            reqs.append(IngestRequest(rid=next(rids), tenant=t, z=z))
        n_q = int(rng.poisson(query_rate))
        if n_q:
            thetas = rng.normal(size=(n_q, dim)).astype(np.float32)
            reqs.append(QueryRequest(rid=next(rids), tenant=t,
                                     thetas=thetas))
    return reqs


def _maybe_fit(gw: StormGateway, args: argparse.Namespace,
               rids: Iterator[int], round_idx: int) -> None:
    """Submit a cohort FitRequest every ``--fit-every`` traffic rounds."""
    if args.fit_every <= 0 or (round_idx + 1) % args.fit_every:
        return
    cohort = list(range(min(args.fit_cohort, args.tenants)))
    gw.submit(FitRequest(rid=next(rids), tenants=cohort,
                         surrogate=args.fit_surrogate, seed=args.seed,
                         steps=args.fit_steps))


def _drive_synthetic(gw: StormGateway, args: argparse.Namespace) -> None:
    rng = np.random.default_rng(args.seed)
    rids = itertools.count()

    # Warm the tick (compile) before timing the serve loop.
    gw.tick()
    t0 = time.perf_counter()
    completed = 0
    if args.pipelined:
        from collections import deque

        inflight = deque()
        for i in range(args.ticks):
            gw.submit_many(synth_traffic(rng, rids, args.tenants, args.dim,
                                         args.ingest_rate, args.query_rate))
            _maybe_fit(gw, args, rids, i)
            inflight.append(gw.tick_start())
            if len(inflight) >= 2:
                completed += len(gw.tick_finish(inflight.popleft()).results)
        while inflight:
            completed += len(gw.tick_finish(inflight.popleft()).results)
        completed += len(gw.run_until_idle(pipelined=True))
    else:
        for i in range(args.ticks):
            gw.submit_many(synth_traffic(rng, rids, args.tenants, args.dim,
                                         args.ingest_rate, args.query_rate))
            _maybe_fit(gw, args, rids, i)
            completed += len(gw.tick().results)
        completed += len(gw.run_until_idle())
    dt = time.perf_counter() - t0

    label = "pipelined" if args.pipelined else "synchronous"
    print(f"served {gw.ticks - 1} {label} ticks over {args.tenants} tenants "
          f"in {dt:.2f}s: {completed} queries answered "
          f"({gw.points_served} points, {gw.points_served / dt:.0f} pts/s), "
          f"{gw.rows_ingested} rows ingested "
          f"({gw.rows_ingested / dt:.0f} rows/s)")
    print(f"tick programs traced {gw.trace_count}x total "
          f"(jit-stable padded shapes)")
    if args.fit_every > 0:
        print(f"cohort fits: {gw.fits_run} x {args.fit_surrogate} over "
              f"{min(args.fit_cohort, args.tenants)} tenants "
              f"({args.fit_steps} DFO steps each, drained between ticks)")
    stats = gw.queue_stats()
    if "privacy" in stats:
        p = stats["privacy"]
        print(f"privacy: {p['mechanism']} eps_total={p['epsilon_total']} "
              f"eps/release={p['epsilon_release']} "
              f"on_exhaust={p['on_exhaust']} -> {p['releases']} releases, "
              f"{len(p['exhausted'])} tenants exhausted, "
              f"{p['queries_refused']} queries refused")
    if hasattr(gw, "tiers"):
        tier = gw.queue_stats()["tier"]
        print(f"tiered bank: T={gw.tenants} hot={tier['hot_capacity']} "
              f"dtype={gw.tiers.dtype.name} "
              f"resident {tier['resident_bytes']:,} B, "
              f"cold {tier['cold_bytes']:,} B host, "
              f"{tier['swap_count']} swaps "
              f"({gw.promotions} promote / {gw.demotions} demote)")
    else:
        print(f"bank: S={gw.tenants} R={gw.params.rows} "
              f"B={gw.params.buckets} ({gw.bank.memory_bytes():,} bytes)")


def _drive_listen(gw: StormGateway, args: argparse.Namespace) -> None:
    from repro.serve.wire import StormWireServer

    host, _, port = args.listen.rpartition(":")
    server = StormWireServer(gw, host or "127.0.0.1", int(port),
                             depth=args.depth).start()
    addr = server.address
    print(f"listening on {addr[0]}:{addr[1]} "
          f"(S={gw.tenants}, I={gw.ingest_slots}, Q={gw.query_slots}, "
          f"caps rows={gw.max_pending_rows} points={gw.max_pending_points})")
    try:
        while True:
            time.sleep(2.0)
            s = gw.queue_stats()
            line = (f"ticks={s['ticks']} pending={s['pending_requests']} "
                    f"rows={s['rows_ingested']} "
                    f"points={s['points_served']} "
                    f"traces={s['trace_count']}")
            if "privacy" in s:
                line += (f" releases={s['privacy']['releases']} "
                         f"exhausted={len(s['privacy']['exhausted'])}")
            print(line)
    except KeyboardInterrupt:
        server.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--dim", type=int, default=8, help="sketch-space dim")
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--planes", type=int, default=4)
    ap.add_argument("--query-slots", type=int, default=32,
                    help="per-tenant theta capacity per tick")
    ap.add_argument("--ingest-slots", type=int, default=128,
                    help="per-tenant row capacity per tick")
    ap.add_argument("--ticks", type=int, default=32)
    ap.add_argument("--ingest-rate", type=int, default=64,
                    help="mean new rows per tenant per tick")
    ap.add_argument("--query-rate", type=int, default=16,
                    help="mean new query points per tenant per tick")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fit-every", type=int, default=0,
                    help="submit a cohort FitRequest every N traffic rounds "
                         "(0 = never; trains from the served counters "
                         "between ticks)")
    ap.add_argument("--fit-cohort", type=int, default=4,
                    help="cohort size for --fit-every (tenants 0..N-1)")
    ap.add_argument("--fit-surrogate", default="prp_regression",
                    help="registered surrogate name for --fit-every")
    ap.add_argument("--fit-steps", type=int, default=50,
                    help="DFO steps per serving-side fit")
    ap.add_argument("--pipelined", action="store_true",
                    help="double-buffered tick loop (overlap host packing "
                         "with device execution)")
    ap.add_argument("--listen", metavar="HOST:PORT", default=None,
                    help="serve the wire protocol instead of synthetic "
                         "traffic (port 0 = ephemeral)")
    ap.add_argument("--depth", type=int, default=2,
                    help="in-flight ticks in the wire engine loop")
    ap.add_argument("--max-pending-rows", type=int, default=None,
                    help="per-tenant ingest-queue cap (backpressure)")
    ap.add_argument("--max-pending-points", type=int, default=None,
                    help="per-tenant query-queue cap (backpressure)")
    ap.add_argument("--hot-capacity", type=int, default=None,
                    help="tiered store: resident slots (< tenants spills "
                         "cold tenants to host; promote/demote overlaps "
                         "the tick)")
    ap.add_argument("--count-dtype", choices=("int32", "int16", "int8"),
                    default="int16",
                    help="tiered resident counter dtype (narrow shrinks "
                         "the device bank; --hot-capacity only)")
    ap.add_argument("--epsilon-total", type=float, default=None,
                    help="per-tenant lifetime eps budget (finite value "
                         "enables privatize-on-read serving; omit for the "
                         "bit-identical non-private gateway)")
    ap.add_argument("--epsilon-release", type=float, default=1.0,
                    help="eps charged per count release (one release per "
                         "tenant per tick covers all its coalesced queries)")
    ap.add_argument("--delta", type=float, default=1e-6,
                    help="gaussian-mechanism delta (--mechanism gaussian)")
    ap.add_argument("--mechanism", choices=("laplace", "gaussian"),
                    default="laplace")
    ap.add_argument("--on-exhaust", choices=("refuse", "stale"),
                    default="refuse",
                    help="exhausted tenants: terminal budget_exceeded "
                         "refusal, or serve the last cached release")
    args = ap.parse_args()
    compile_cache.enable()

    policy = None
    if args.epsilon_total is not None:
        from repro.core.privacy import ReleasePolicy

        policy = ReleasePolicy(epsilon_total=args.epsilon_total,
                               epsilon_release=args.epsilon_release,
                               delta=args.delta, mechanism=args.mechanism,
                               on_exhaust=args.on_exhaust)

    params = lsh.init_srp(jax.random.PRNGKey(args.seed), args.rows,
                          args.planes, args.dim + 2)
    if args.hot_capacity is not None:
        from repro.serve.tiered_gateway import TieredStormGateway

        gw = TieredStormGateway(params, args.tenants, args.hot_capacity,
                                query_slots=args.query_slots,
                                ingest_slots=args.ingest_slots,
                                count_dtype=np.dtype(args.count_dtype),
                                max_pending_rows=args.max_pending_rows,
                                max_pending_points=args.max_pending_points,
                                privacy=policy, privacy_seed=args.seed)
    else:
        gw = StormGateway(params, args.tenants,
                          query_slots=args.query_slots,
                          ingest_slots=args.ingest_slots,
                          max_pending_rows=args.max_pending_rows,
                          max_pending_points=args.max_pending_points,
                          privacy=policy, privacy_seed=args.seed)
    if args.listen is not None:
        _drive_listen(gw, args)
    else:
        _drive_synthetic(gw, args)


if __name__ == "__main__":
    main()
