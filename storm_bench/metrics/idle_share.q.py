"""Percent of the window in which no operation ran on the device (profiler
trace), in the read cells."""

from storm_bench.layers import idle_share as read  # noqa: F401
