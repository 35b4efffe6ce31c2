"""Percent of the window in which no operation ran on the device (profiler
trace), in the ingest cell."""

from storm_bench.layers import idle_share as read  # noqa: F401
