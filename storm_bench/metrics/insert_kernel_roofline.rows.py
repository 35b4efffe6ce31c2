"""Percent of its roofline the banked insert kernel (kernels/storm_sketch.py)
reached over the window."""

from storm_bench.layers import roofline


def read(run):
    return roofline(run, "insert")
