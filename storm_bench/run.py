"""Runs one cell of the STORM benchmark once.

    python3 storm_bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up builds the cell's gateway on the chip from the seed, fills every
tenant with ``fill_rows`` rows through the gateway, and warms the tick
programs the cell's traffic uses. The window then drives the gateway's
double-buffered engine loop (``serve.py``) with the cell's traffic
(``traffic/<kind>.py``) for ``--seconds``. Requests due in the window are
followed to completion; then the device's peak memory is read, the
program's state is copied out and freed, and a sample of what the window
served is compared with the plain reference (``check.py``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. The last line of
standard output is one JSON object; the numbers compared for ``correct``
are the last lines of standard error and the last key of that object.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result. Options the benchmark's own runs do not use:
``--rehearse`` takes a test-only cell from ``rehearsal.json`` and runs on
the CPU (Pallas in interpret mode) with its metrics under ``cpu_metrics``;
``--control`` puts the reference at one precision step below the stated one
in the program's place for the comparison; ``--sweep r1,r2,..`` measures an
open loop at several rates after one set-up, with no check.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from storm_bench import cells, check, payloads  # noqa: E402
from storm_bench.serve import (INGEST, QUERY, SETUP, WINDOW,  # noqa: E402
                               annotate, clock)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", default="")
    return ap.parse_args(argv)


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def devices(cell, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:cell.chips]


def hyperplanes(cfg: dict):
    """The deployment's hash family, ``(R, p, dim + 2)`` float32, made on
    the device in one call from the configuration's ``hash_seed``.

    A deployment fixes its family for the life of its sketches. The tick
    programs hold it as a compiled-in constant, so a family drawn from the
    run's seed would make every run compile anew. The benchmark draws it
    itself (the same draw as ``lsh.init_srp``), because the reference may
    take no table that the program has made.
    """
    import jax

    shape = (cfg["rows"], cfg["planes"], cfg["features"] + 3)
    return jax.jit(lambda k: jax.random.normal(k, shape))(
        jax.random.key(cfg["hash_seed"]))


def build(args, cell):
    import jax.numpy as jnp

    from repro.core import lsh
    from repro.serve.storm_gateway import StormGateway
    from storm_bench.serve import Server

    cfg, traffic = cell.config, cell.traffic
    w = hyperplanes(cfg)
    gw = StormGateway(lsh.LSHParams(projections=w), cfg["tenants"],
                      query_slots=cfg["query_slots"],
                      ingest_slots=cfg["ingest_slots"],
                      count_dtype=jnp.dtype(cfg["count_dtype"]),
                      mode="interpret" if args.rehearse else "kernel")
    rows = payloads.row_pool(payloads.rng_of(args.seed, 1), cfg["features"],
                             traffic["ingest_rows"], cfg["noise"],
                             cfg["condition"])
    thetas = payloads.theta_pool(payloads.rng_of(args.seed, 2),
                                 cfg["features"] + 1,
                                 traffic.get("query_points", 1))
    return Server(gw, rows, thetas), np.asarray(w)


def prepare(server, cell, programs, seed: int) -> None:
    """Fill every tenant, then run each tick program the traffic uses
    twice, so that the window compiles and loads nothing."""
    s = cell.config["tenants"]
    per = cell.traffic["ingest_rows"]
    rng = payloads.rng_of(seed, 4)

    def serve(reqs):
        with server.lock:
            for kind, tenant in reqs:
                server.submit(kind, tenant,
                              int(rng.integers(payloads.POOL_BLOCKS)),
                              clock(), SETUP)
        server.run(lambda: True)

    fill = cell.traffic.get("fill_rows", 0) // per
    serve([(INGEST, t) for _ in range(fill) for t in range(s)])
    warm = {"ingest": [(INGEST, 0)], "query": [(QUERY, 0)],
            "full": [(INGEST, 0), (QUERY, 0)]}
    for name in sorted(programs):
        for _ in range(2):
            serve(warm[name])


def e2e_metrics(log, t0: float, seconds: float, setup_s: float,
                query_points: int, ingest_rows: int, open_loop: bool) -> dict:
    """An open loop counts every request due in the window, followed to
    completion; a closed loop counts what was answered inside the window."""
    win = log["phase"] == WINDOW
    ok = np.isfinite(log["done"]) & ~log["failed"]
    if open_loop:
        counted = win & ok
    else:
        counted = ok & (log["done"] >= t0) & (log["done"] < t0 + seconds)
    values = {"setup_s": setup_s}
    q = win & (log["kind"] == QUERY)
    if q.any():
        lat = np.where(ok[q], log["done"][q] - log["due"][q], np.inf)
        values["query_p99_ms"] = float(np.percentile(lat, 99) * 1e3)
        values["query_points_per_s"] = float(
            (counted & (log["kind"] == QUERY)).sum() * query_points / seconds)
    if (log["kind"][win] == INGEST).any():
        values["ingest_rows_per_s"] = float(
            (counted & (log["kind"] == INGEST)).sum() * ingest_rows / seconds)
    return values


def sweep(args, cell, server, gen_mod, rates) -> None:
    for rate in rates:
        gen = gen_mod.Driver(cell.traffic, cell.config, args.seed,
                             args.seconds, rate)
        start = len(server.kind)
        t0 = clock()
        gen.start(server, t0)
        server.run(gen.closing, gen.on_done)
        gen.join()
        log = {k: v[start:] for k, v in server.table().items()}
        q = (log["phase"] == WINDOW) & (log["kind"] == QUERY)
        lat = (log["done"][q] - log["due"][q]) * 1e3
        late = log["due"][q] >= t0 + 0.75 * args.seconds
        early = log["due"][q] < t0 + 0.25 * args.seconds
        print(json.dumps({
            "rate": rate, "requests": int(q.sum()),
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "early_mean_ms": float(np.nanmean(lat[early])),
            "late_mean_ms": float(np.nanmean(lat[late])),
            "drain_s": clock() - t0 - args.seconds,
            **gen.lag_summary()}), flush=True)


def main(argv=None) -> dict:
    args = parse(argv)
    cell = cells.load_cell(args.workload, rehearsal=args.rehearse)
    setup = {"imported": clock() - T_START}  # seconds from start, per phase
    devs = devices(cell, args.rehearse)
    setup["devices"] = clock() - T_START
    import jax

    if not args.rehearse:
        from repro.launch import compile_cache

        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from storm_bench import trace as trace_lib

    gen_mod = cells.generator(cell.traffic["kind"])
    server, w = build(args, cell)
    setup["built"] = clock() - T_START
    rates = [float(r) for r in args.sweep.split(",")] if args.sweep else []
    gen = gen_mod.Driver(cell.traffic, cell.config, args.seed, args.seconds,
                         *rates[:1])
    prepare(server, cell, gen.programs, args.seed)
    setup["warm"] = clock() - T_START
    if rates:
        return sweep(args, cell, server, gen_mod, rates)

    trace_dir = cells.out_dir("trace", cell.name)
    if args.trace:
        for old in trace_dir.rglob("*"):
            if old.is_file():
                old.unlink()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t0 = clock()
    setup_s = t0 - T_START
    with annotate("storm.window"):
        pass
    gen.start(server, t0)
    stopper = None
    if args.trace:
        import threading

        stopper = threading.Timer(args.seconds, jax.profiler.stop_trace)
        stopper.start()
    server.run(gen.closing, gen.on_done)
    gen.join()
    if stopper is not None:
        stopper.join()
    ticks_in_window = [t for t in server.ticks
                       if t0 <= t[0] < t0 + args.seconds]
    counters = {k: v for k, v in server.gw.queue_stats().items()
                if not isinstance(v, list)}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    log = server.table()
    cfg, traffic = cell.config, cell.traffic
    sent = check.rows_sent(log, cfg["tenants"], traffic["ingest_rows"])
    counted, hash_rows, rids = check.sample(log, cfg["tenants"], cfg["rows"],
                                            args.seed)
    tenants_read = sorted(set(counted.tolist())
                          | set(log["tenant"][rids].tolist()))
    got = check.program_state(server.gw, tenants_read, sent)
    got["answers"] = server.answers
    win = log["phase"] == WINDOW
    unanswered = int((win & (~np.isfinite(log["done"]) | log["failed"])
                      ).sum())
    server.gw = None  # the program's state is freed before the reference
    import gc

    gc.collect()

    t_check = clock()
    from storm_bench.reference import Reference

    tick_starts = np.asarray([t[0] for t in server.ticks])
    replay = functools.partial(check.replay, log=log, rows=server.rows,
                               thetas=server.thetas, counted=counted,
                               hash_rows=hash_rows, rids=rids,
                               tick_starts=tick_starts)
    expected = replay(Reference(w))
    if args.control:
        got = check.control_state(*replay(Reference(w, "high"), paths=False),
                                  sent)
    numbers = check.compare(expected, got, sent, unanswered, log["tenant"])
    check_s = clock() - t_check

    e2e = e2e_metrics(log, t0, args.seconds, setup_s,
                      traffic.get("query_points", 0), traffic["ingest_rows"],
                      traffic["kind"] == "open_loop")
    result = {"correct": check.correct(numbers),
              "attempted": int(win.sum()),
              "failed": unanswered}
    run_info = {"generator": gen.lag_summary(), "setup": setup,
                "check_s": check_s,
                "ticks_in_window": len(ticks_in_window),
                "counters": counters, "e2e": e2e}
    if args.trace:
        events = trace_lib.extract(trace_dir)
        try:
            summary = trace_lib.summarize(events, args.seconds)
        except ValueError:
            if not args.rehearse:
                raise
            summary = None  # the CPU backend has no device plane
        run = {"config": cfg, "traffic": traffic, "seconds": args.seconds,
               "ticks": np.asarray(ticks_in_window).reshape(-1, 4),
               "trace": events, "summary": summary,
               "peaks": cells.peaks(devs[0].device_kind)
               if not args.rehearse else None}
        metrics = {}
        for m in cell.per_layer:
            value = cells.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if summary is not None:
            run_info["host_s"] = summary["host_s"]
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
        result["device"] = device
    if args.rehearse:
        result["cpu_metrics"] = result.pop("metrics")
    result["checks"] = numbers

    say("generator lag:", json.dumps(run_info["generator"]))
    say("run:", json.dumps({k: v for k, v in run_info.items()
                            if k != "counters"}))
    out = cells.out_dir("runs") / (f"{cell.name}.{args.seed}.{args.trace}"
                                   f"{'.control' if args.control else ''}"
                                   ".json")
    out.write_text(json.dumps(dict(result, run=run_info), indent=1))
    for name, v in numbers.items():
        say(f"check {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
