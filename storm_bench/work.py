"""The work a kernel's algorithm needs, from the real traffic it carried.

Counts follow the algorithm, never the implementation: ``points`` and
``rows`` are the real query points and ingest rows a tick carried (the
gateway's counters), not its padded slots, so removing padding or the
per-bucket table select changes the time and not the count.

* query: ``2·points·D·R·p`` projection flops; bytes are the query vectors
  and the hash weights, the answers, and the counters the points read: the
  smaller of ``points·R`` counters and the whole bank;
* insert: ``2·rows·D·R·p`` flops (one antithetic projection pass serves
  both signs); bytes are the rows in and the bank read and written once.

``D = features + 3`` is the augmented width, ``dim + 2``.
"""

from __future__ import annotations

import numpy as np

F32 = 4


def _shape(cfg: dict):
    d = cfg["features"] + 3
    r, p = cfg["rows"], cfg["planes"]
    bank = cfg["tenants"] * r * (1 << p) * np.dtype(cfg["count_dtype"]).itemsize
    return d, r, p, bank


def query(points, cfg: dict):
    """``(flops, bytes)`` of answering ``points`` query points."""
    d, r, p, bank = _shape(cfg)
    points = np.asarray(points, float)
    itemsize = np.dtype(cfg["count_dtype"]).itemsize
    flops = 2 * points * d * r * p
    nbytes = (F32 * points * (d + 1) + F32 * r * p * d
              + np.minimum(points * r * itemsize, bank))
    return flops, nbytes


def insert(rows, cfg: dict):
    """``(flops, bytes)`` of inserting ``rows`` ingest rows (one launch)."""
    d, r, p, bank = _shape(cfg)
    rows = np.asarray(rows, float)
    flops = 2 * rows * d * r * p
    nbytes = F32 * rows * (d - 2) + F32 * r * p * d + 2 * bank
    return flops, nbytes


def least_seconds(flops, nbytes, peaks: dict):
    """The least time the chip could take: the larger of the two bounds."""
    return np.maximum(np.asarray(flops) / peaks["flops_per_s"],
                      np.asarray(nbytes) / peaks["bytes_per_s"])
