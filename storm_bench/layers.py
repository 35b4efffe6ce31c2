"""Reductions that the per-layer readers (``metrics/<name>.py``) share.

A reader takes the run (``run.py`` builds it: the configuration, the ticks
dispatched in the window, the extracted trace and its summary, the peak
table) and returns a number, or ``None`` where the run has nothing to read;
the harness then leaves the metric out. None of them returns 0 for a share
of a roofline.
"""

from __future__ import annotations

import numpy as np

from storm_bench import trace as trace_lib
from storm_bench import work

# The HLO names a TPU trace gives each kernel's custom call: the jitted
# entry point that launches it (``%sketch_query_banked.1 = ...
# custom-call(...)``), or the kernel function's own name.
KERNELS = {"query": ("sketch_query_banked", "_query_kernel"),
           "insert": ("hash_histogram_banked", "_insert_kernel")}
# ticks: (host start, tick_start seconds, points, rows)
START_S, POINTS, ROWS = 1, 2, 3


def tick_start_ms(run, column: int):
    """Mean host milliseconds of ``tick_start`` over the window's ticks
    that carried traffic of the given kind."""
    t = run["ticks"]
    t = t[t[:, column] > 0] if len(t) else t
    return float(t[:, START_S].mean() * 1e3) if len(t) else None


def idle_share(run):
    s = run["summary"]
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def _kernel(run, kind):
    """Summed seconds and launches of a kernel's device events in the
    window."""
    if run["summary"] is None:
        return 0.0, 0
    lo, hi = trace_lib.window(run["trace"], run["seconds"])
    ops = next(iter(run["trace"]["device"].values()))
    ns, count = trace_lib.kernel_ns(ops, KERNELS[kind], lo, hi)
    return ns / 1e9, count


def roofline(run, kind: str):
    """Percent of its roofline the kernel reached: the least time its
    launches needed, over the time they took. Each tick launches the kernel
    once, so the least time per launch is averaged over the window's ticks
    of that kind and multiplied by the launches the trace holds."""
    seconds, launches = _kernel(run, kind)
    t = run["ticks"]
    column = POINTS if kind == "query" else ROWS
    t = t[t[:, column] > 0] if len(t) else t
    if not launches or not len(t) or run["peaks"] is None:
        return None
    fn = work.query if kind == "query" else work.insert
    least = work.least_seconds(*fn(t[:, column], run["config"]), run["peaks"])
    return float(100.0 * np.mean(least) * launches / seconds)
