"""Mean host milliseconds of the gateway's tick_start (packing and dispatch)
over the window's query-carrying ticks."""

from storm_bench.layers import POINTS, tick_start_ms


def read(run):
    return tick_start_ms(run, POINTS)
