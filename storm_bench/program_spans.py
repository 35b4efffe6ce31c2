"""Readers of the spans the program records inside its tick.

``StormGateway`` opens ``storm.gw.*`` spans (``jax.profiler``
``TraceAnnotation``) around the stages of ``tick_start`` and
``tick_finish``: ``pack_ingest`` and ``pack_queries`` (host packing),
``flatten`` (the one fused buffer, or the sharded ``device_put`` calls on a
mesh), ``launch`` (the call of the tick program, which copies the buffer to
the device), ``readback``, ``scatter`` (results into the requests), ``fits``
and ``trace`` (one per trace of a tick program). They share the profiler's
clock with the device's operations, and ``trace.extract`` picks them up with
the harness's own ``storm.*`` spans. A reader takes the spans that start in
the window and returns ``None`` where there are none, as in a program that
records no such span; never 0.
"""

from __future__ import annotations

from storm_bench import trace as trace_lib

PREFIX = "storm.gw."


def durations_ms(run, stage: str) -> list:
    """Milliseconds of each ``storm.gw.<stage>`` span that starts in the
    window."""
    lo, hi = trace_lib.window(run["trace"], run["seconds"])
    name = PREFIX + stage
    return [d / 1e6 for n, s, d in run["trace"]["spans"]
            if n == name and lo <= s < hi]


def mean_ms(run, stage: str):
    """Mean milliseconds of a stage's spans in the window."""
    ms = durations_ms(run, stage)
    return sum(ms) / len(ms) if ms else None


def dispatch_ms(run):
    """Milliseconds a tick spends from its packed buffers to a dispatched
    program: the fused buffer (``flatten``) and the call that copies it to
    the device (``launch``), summed over the window and divided by the
    ticks launched."""
    launches = durations_ms(run, "launch")
    if not launches:
        return None
    return (sum(durations_ms(run, "flatten")) + sum(launches)) / len(launches)
