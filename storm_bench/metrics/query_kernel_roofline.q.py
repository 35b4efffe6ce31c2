"""Percent of its roofline the banked query kernel (kernels/sketch_query.py)
reached over the window."""

from storm_bench.layers import roofline


def read(run):
    return roofline(run, "query")
