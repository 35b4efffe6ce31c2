"""The STORM regression deployment: one ``StormGateway`` of paired-random-
projection (PRP) sketches, fed regression rows ``[x, y]`` and asked sketch
losses at Gaussian thetas, checked against ``reference.py``.

A configuration names its deployment by its ``deployment`` key; the harness
loads ``deployments/<name>.py`` and calls

    build(cell, devices, seed, rehearse) -> deployment

before the window. The deployment it returns gives the harness:

* ``server``: the engine loop the window drives, with ``serve.Server``'s
  interface (``lock``, ``submit``, ``run``, ``table``, ``ticks``), which the
  traffic generators submit to;
* ``carried``: ``{INGEST: rows, QUERY: points}``, what one logged request
  of each kind carried, which the end-to-end rates count;
* ``warm(programs, seed)``: the set-up the traffic needs, and each program
  it uses (``"ingest"``, ``"query"``, ``"full"``) run, so that the window
  compiles nothing;
* ``layout()`` and ``counters()``: how the program was laid over the
  devices, and its own counters, read after the window;
* ``check(log, seed, unanswered, control)``: the numbers that
  ``check.correct`` judges, each beside its limit. It copies out what it
  compares, frees the program's state, and only then runs the reference.

An optional ``compile_ticks(config, devices)`` yields ``(program,
compiled)`` for described devices, for ``aot_compile.py``.

This module reads the configuration's ``features``, ``noise``,
``condition``, ``rows``, ``planes``, ``paired``, ``hash_seed``,
``count_dtype``, ``tenants``, ``ingest_slots`` and ``query_slots``, and the
traffic's ``ingest_rows``, ``query_points`` and ``fill_rows``. A cell on
one chip runs the meshless gateway; on more, the gateway splits the bank
over exactly the cell's devices (``Mesh(devices, ("bank",))``).
"""

from __future__ import annotations

import functools
import gc

import numpy as np

from storm_bench import check, payloads
from storm_bench.serve import INGEST, QUERY, SETUP, clock


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


def gateway(cfg: dict, w, devices, mode: str):
    """The cell's ``StormGateway``: meshless on one device, else the bank
    split over exactly ``devices``."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core import lsh
    from repro.serve.storm_gateway import StormGateway

    if not cfg["paired"]:
        raise SystemExit(
            f"{cfg['name']}: 'paired' is false, but the regression "
            "deployment's reference (reference.py) is paired only; a "
            "single-sided deployment needs a module of its own under "
            "storm_bench/deployments/")
    mesh = Mesh(np.array(devices), ("bank",)) if len(devices) > 1 else None
    return StormGateway(lsh.LSHParams(projections=w), cfg["tenants"],
                        paired=cfg["paired"],
                        query_slots=cfg["query_slots"],
                        ingest_slots=cfg["ingest_slots"],
                        count_dtype=jnp.dtype(cfg["count_dtype"]),
                        mode=mode, mesh=mesh, axis="bank")


def build(cell, devices, seed: int, rehearse: bool) -> "Regression":
    return Regression(cell, devices, seed, rehearse)


class Regression:
    def __init__(self, cell, devices, seed: int, rehearse: bool):
        from storm_bench.serve import Server

        cfg, traffic = cell.config, cell.traffic
        # Pallas in interpret mode cannot run inside shard_map, so a
        # rehearsal over several CPU devices takes the jnp path.
        mode = "kernel" if not rehearse else (
            "interpret" if len(devices) == 1 else "ref")
        w = hyperplanes(cfg)
        gw = gateway(cfg, w, devices, mode)
        rows = payloads.row_pool(payloads.rng_of(seed, 1), cfg["features"],
                                 traffic["ingest_rows"], cfg["noise"],
                                 cfg["condition"])
        thetas = payloads.theta_pool(payloads.rng_of(seed, 2),
                                     cfg["features"] + 1,
                                     traffic.get("query_points", 1))
        self.cell = cell
        self.w = np.asarray(w)
        self.server = Server(gw, rows, thetas)
        self.carried = {INGEST: traffic["ingest_rows"],
                        QUERY: traffic.get("query_points", 0)}

    def warm(self, programs, seed: int) -> None:
        """Fill every tenant, then run each tick program the traffic uses
        twice, so that the window compiles and loads nothing."""
        server = self.server
        s = self.cell.config["tenants"]
        per = self.cell.traffic["ingest_rows"]
        rng = payloads.rng_of(seed, 4)

        def serve(reqs):
            with server.lock:
                for kind, tenant in reqs:
                    server.submit(kind, tenant,
                                  int(rng.integers(payloads.POOL_BLOCKS)),
                                  clock(), SETUP)
            server.run(lambda: True)

        fill = self.cell.traffic.get("fill_rows", 0) // per
        serve([(INGEST, t) for _ in range(fill) for t in range(s)])
        warm = {"ingest": [(INGEST, 0)], "query": [(QUERY, 0)],
                "full": [(INGEST, 0), (QUERY, 0)]}
        for name in sorted(programs):
            for _ in range(2):
                serve(warm[name])

    def layout(self) -> dict:
        gw = self.server.gw
        return {"mesh": None if gw.mesh is None else dict(gw.mesh.shape),
                "bank_devices": len(gw.bank.counts.sharding.device_set),
                "mode": gw.mode}

    def counters(self) -> dict:
        return {k: v for k, v in self.server.gw.queue_stats().items()
                if not isinstance(v, list)}

    def check(self, log: dict, seed: int, unanswered: int,
              control: bool) -> dict:
        from storm_bench.reference import Reference

        server, cfg = self.server, self.cell.config
        sent = check.rows_sent(log, cfg["tenants"], self.carried[INGEST])
        counted, hash_rows, rids = check.sample(log, cfg["tenants"],
                                                cfg["rows"], seed)
        tenants_read = sorted(set(counted.tolist())
                              | set(log["tenant"][rids].tolist()))
        got = check.program_state(server.gw, tenants_read, sent)
        got["answers"] = server.answers
        server.gw = None  # the program's state is freed before the reference
        gc.collect()

        tick_starts = np.asarray([t[0] for t in server.ticks])
        replay = functools.partial(check.replay, log=log, rows=server.rows,
                                   thetas=server.thetas, counted=counted,
                                   hash_rows=hash_rows, rids=rids,
                                   tick_starts=tick_starts)
        expected = replay(Reference(self.w))
        if control:
            got = check.control_state(
                *replay(Reference(self.w, "high"), paths=False), sent)
        return check.compare(expected, got, sent, unanswered, log["tenant"])


def compile_ticks(cfg: dict, devices):
    """Each tick program of the configuration's gateway, compiled for the
    described ``devices``: ``(name, compiled)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    gw = gateway(cfg, hyperplanes(cfg), devices, "kernel")
    s, r, p = cfg["tenants"], cfg["rows"], cfg["planes"]
    dim, i_cap, q_cap = cfg["features"] + 1, cfg["ingest_slots"], \
        cfg["query_slots"]
    f32 = jnp.float32
    zbuf, zmask = ((s, i_cap, dim), f32), ((s, i_cap), f32)
    qbuf, qmask = ((s * q_cap, dim), f32), ((s * q_cap,), f32)
    programs = {"full": [zbuf, zmask, qbuf, qmask], "ingest": [zbuf, zmask],
                "query": [qbuf, qmask]}
    if gw.mesh is None:  # the meshless programs take one fused buffer
        where = SingleDeviceSharding(devices[0])
        programs = {k: [((sum(int(np.prod(shape)) for shape, _ in v),), f32)]
                    for k, v in programs.items()}
    else:
        where = NamedSharding(gw.mesh, PartitionSpec("bank"))
    bank = [((s, r, 1 << p), jnp.dtype(cfg["count_dtype"])),
            ((s,), jnp.int32)]
    for prog, buffers in programs.items():
        args = [jax.ShapeDtypeStruct(shape, dt, sharding=where)
                for shape, dt in bank + buffers]
        yield prog, getattr(gw, f"_tick_{prog}").lower(*args).compile()
