"""Percent of the query slots of the window's query-carrying ticks that held
a real point: points served over (ticks x tenants x query_slots)."""

from storm_bench.layers import POINTS


def read(run):
    t = run["ticks"]
    t = t[t[:, POINTS] > 0] if len(t) else t
    if not len(t):
        return None
    cfg = run["config"]
    slots = len(t) * cfg["tenants"] * cfg["query_slots"]
    return float(100.0 * t[:, POINTS].sum() / slots)
