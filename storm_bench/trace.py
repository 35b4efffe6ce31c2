"""From a profiler trace to device busy time, kernel time and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of events (nanoseconds): the device's operations (the "XLA Ops"
line of each TPU plane) and the harness's host spans (names starting with
``storm.``). Everything else works on that dict, so the reduction is checked
on a small recorded trace (``fixtures/``) without a chip.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "storm."


def _newest(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def extract(log_dir) -> dict:
    """``{"device": {plane: [[name, start, dur], ...]}, "spans": [[name,
    start, dur], ...]}`` from the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(_newest(log_dir)))
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return {"device": device, "spans": spans}


def _merged(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = np.clip(intervals[np.argsort(intervals[:, 0])], lo, hi)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _intervals(ops) -> np.ndarray:
    return np.asarray([[s, s + d] for _, s, d in ops], float).reshape(-1, 2)


def busy_ns(ops, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some operation ran."""
    m = _merged(_intervals(ops), lo, hi)
    return float((m[:, 1] - m[:, 0]).sum()) if len(m) else 0.0


def idle_gaps(ops, lo: float, hi: float):
    """``(start, end)`` of each stretch of ``[lo, hi]`` with no operation."""
    m = _merged(_intervals(ops), lo, hi)
    edges = np.concatenate([[lo], m.ravel(), [hi]]).reshape(-1, 2)
    return [(s, e) for s, e in edges if e > s]


def kernel_ns(ops, names, lo: float, hi: float) -> tuple:
    """Summed duration and count of a kernel's launches that start inside
    ``[lo, hi]``. A TPU trace names each operation by its HLO text; a
    Pallas kernel is the ``custom-call`` whose own name (before `` = ``)
    holds one of ``names``."""
    def hit(op):
        head, _, body = op[0].partition(" = ")
        return any(n in head for n in names) and (
            not body or "custom-call(" in body)

    hits = [op[2] for op in ops if lo <= op[1] < hi and hit(op)]
    return float(sum(hits)), len(hits)


def label(gap, spans) -> str:
    """The host span that covers most of ``gap``, else ``engine idle``."""
    best, cover = "engine idle", 0.0
    for name, s, d in spans:
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > cover:
            best, cover = name, c
    return best


def window(trace: dict, seconds: float) -> tuple:
    """``(lo, hi)`` of the measured window: from the ``storm.window``
    marker for ``seconds``."""
    starts = [s for n, s, _ in trace["spans"] if n == "storm.window"]
    if not starts:
        raise ValueError("no storm.window marker in the trace")
    return starts[0], starts[0] + seconds * 1e9


def summarize(trace: dict, seconds: float, top: int = 10) -> dict:
    """Busy seconds and window seconds averaged over the chips traced, the
    host seconds of each harness span that starts in the window, and the
    breakdown: the device operations that took most time, and the longest
    idle gaps labelled by the host span open in them."""
    lo, hi = window(trace, seconds)
    planes = list(trace["device"].values())
    if not planes:
        raise ValueError("no device operations in the trace")
    busy = np.mean([busy_ns(ops, lo, hi) for ops in planes]) / 1e9
    totals = {}
    for n, s, d in planes[0]:
        if lo <= s < hi:
            totals[n] = totals.get(n, 0) + d / 1e9
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(planes[0], lo, hi), key=lambda g: g[0] - g[1])
    host = {}
    for n, s, d in trace["spans"]:
        if lo <= s < hi:
            host[n] = host.get(n, 0) + d / 1e9
    return {
        "host_s": host,
        "busy_s": float(busy),
        "window_s": float(seconds),
        "device_ops": [[n.partition(" = ")[0], s] for n, s in ops],
        "idle_gaps": [[label(g, trace["spans"]), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top]],
    }
