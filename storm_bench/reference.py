"""Plain reference of the STORM sketch service, in numpy.

It imports nothing of the program under test. Semantics (the STORM paper,
arXiv:2006.14554, and DESIGN.md §3):

* a deployment shares one hash family: ``R`` rows of ``p`` Gaussian
  hyperplanes in the augmented space of ``D = dim + 2`` coordinates; a
  vector's code in row ``r`` packs the signs of its ``p`` projections,
  plane ``j`` into bit ``j``;
* ingesting a row ``z`` (``|z| <= 1``) adds one count in each row at the
  code of ``[z, 0, sqrt(1 - |z|^2)]`` and one at the code of
  ``[-z, 0, sqrt(1 - |z|^2)]`` (paired random projections);
* a query theta is scaled to the unit sphere, augmented to
  ``[q, sqrt(1 - |q|^2), 0]``, and answers the mean over rows of the
  tenant's count at its code, divided by ``2 max(n, 1)`` for ``n`` rows
  ingested.

Projections are float32 products (the configuration's precision).
``precision="high"`` computes them as three bfloat16 passes with float32
accumulation (``hi*hi + hi*lo + lo*hi``), which is what ``Precision.HIGH``
does on a TPU: that is the control, one step below the stated precision.

A query's pad ``sqrt(1 - |q|^2)`` is 0 in exact arithmetic, but a query
scaled to unit norm in float32 has ``|q|^2`` off 1 by rounding, so the pad
any float32 implementation computes is some value in ``[0, PAD_MAX]``.
``query_paths`` therefore gives every answer a pad in that range can give:
the projections are taken in float64 and the pad swept over the range, so
only the code changes that the pad moves are allowed, not independent sign
flips.
"""

from __future__ import annotations

import numpy as np

CHUNK = 4096  # rows hashed per block, so that codes stay small in memory
# sqrt of the most that float32 rounding can leave of 1 - |q|^2 for a query
# scaled to unit norm (a few ulps of 1, about 6e-7), with room: 2e-3.
PAD_MAX = 2e-3


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept as f32."""
    u = x.astype(np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _unit(t: np.ndarray) -> np.ndarray:
    return t / np.maximum(np.sqrt((t * t).sum(1, dtype=t.dtype)),
                          t.dtype.type(1e-12))[:, None]


class Reference:
    def __init__(self, hyperplanes: np.ndarray, precision: str = "highest"):
        """``hyperplanes``: ``(R, p, D)`` float32."""
        self.hyperplanes = np.asarray(hyperplanes, np.float32)
        self.rows, self.planes, self.dim = self.hyperplanes.shape
        self.buckets = 1 << self.planes
        if precision not in ("highest", "high"):
            raise ValueError(precision)
        self.precision = precision
        self._w = {}

    def _weights(self, rows):
        """``(D, p * k)`` columns plane-major (``j * k + i`` for the i-th of
        the ``k`` hash rows ``rows``), and their bfloat16 halves."""
        key = None if rows is None else tuple(rows)
        if key not in self._w:
            h = self.hyperplanes if rows is None else self.hyperplanes[rows]
            w = np.ascontiguousarray(np.transpose(h, (1, 0, 2)).reshape(
                -1, self.dim).T)
            hi = _bf16(w)
            self._w[key] = (w, hi, _bf16(w - hi))
        return self._w[key]

    def codes(self, x: np.ndarray, rows=None) -> np.ndarray:
        """``(n, D)`` float32 -> ``(n, k)`` codes in the hash rows ``rows``
        (all ``R`` when None)."""
        x = x.astype(np.float32)
        w, w_hi, w_lo = self._weights(rows)
        if self.precision == "highest":
            proj = x @ w
        else:
            hi = _bf16(x)
            lo = _bf16(x - hi)
            proj = hi @ w_hi + hi @ w_lo + lo @ w_hi
        k = w.shape[1] // self.planes
        proj = proj.reshape(len(x), self.planes, k)
        codes = np.zeros((len(x), k), np.uint8)
        for j in range(self.planes):
            codes |= (proj[:, j] > 0).view(np.uint8) << np.uint8(j)
        return codes

    def empty(self, rows=None) -> np.ndarray:
        k = self.rows if rows is None else len(rows)
        return np.zeros((k, self.buckets), np.int64)

    def insert(self, table: np.ndarray, z: np.ndarray, rows=None) -> None:
        """Add the paired counts of rows ``z`` (``(n, dim)``) to ``table``,
        which holds the hash rows ``rows`` (all when None)."""
        offset = np.arange(len(table), dtype=np.int32) * self.buckets
        for lo in range(0, len(z), CHUNK):
            zc = z[lo:lo + CHUNK].astype(np.float32)
            pad = np.sqrt(np.clip(1.0 - (zc * zc).sum(1, dtype=np.float32),
                                  0.0, None))[:, None]
            zero = np.zeros_like(pad)
            for sign in (1.0, -1.0):
                flat = self.codes(np.concatenate([sign * zc, zero, pad], 1),
                                  rows) + offset
                table += np.bincount(flat.ravel(), minlength=table.size
                                     ).reshape(table.shape)

    def query(self, table: np.ndarray, n: int,
              thetas: np.ndarray) -> np.ndarray:
        """Answers ``(m,)`` float64 for thetas ``(m, dim)`` against one
        tenant's table holding ``n`` rows, with the pad this reference's
        own float32 rounding gives."""
        q = _unit(thetas.astype(np.float32))
        pad = np.sqrt(np.clip(1.0 - (q * q).sum(1, dtype=np.float32), 0.0,
                              None))[:, None]
        codes = self.codes(np.concatenate([q, pad, np.zeros_like(pad)], 1))
        hits = table[np.arange(self.rows), codes.astype(np.intp)]  # (m, R)
        return hits.mean(axis=1) / (2.0 * max(n, 1))

    def query_paths(self, table: np.ndarray, n: int,
                    thetas: np.ndarray) -> list:
        """For each theta, every answer that a pad in ``[0, PAD_MAX]``
        gives: a float64 array of one to a few values.

        The projection of ``[q, pad, 0]`` is ``a + pad * b`` with ``a`` the
        projection of the unit query and ``b`` the pad's weight, so a code
        bit flips only where ``pad = -a / b``. Walking the pad up from 0
        through those points flips one bit at a time; each stretch between
        two of them gives one answer."""
        q = _unit(thetas.astype(np.float64))
        w = np.transpose(self.hyperplanes, (1, 0, 2)).reshape(
            -1, self.dim).astype(np.float64)  # (p * R, D), plane-major
        a = q @ w[:, :-2].T                        # (m, p * R)
        b = w[:, -2]
        pos = (a > 0).reshape(len(a), self.planes, self.rows)
        codes = (pos << np.arange(self.planes)[:, None]).sum(1)   # (m, R)
        rows = np.arange(self.rows)
        base = table[rows[None], codes].sum(1)
        scale = 1.0 / (self.rows * 2.0 * max(n, 1))
        out = []
        with np.errstate(divide="ignore", invalid="ignore"):
            cross = -a / b
        for i in range(len(a)):
            hit = np.flatnonzero((cross[i] > 0) & (cross[i] < PAD_MAX))
            code, total, sums = codes[i].copy(), int(base[i]), [int(base[i])]
            for k in hit[np.argsort(cross[i][hit])]:
                j, r = divmod(int(k), self.rows)
                new = code[r] ^ (1 << j)
                total += int(table[r, new] - table[r, code[r]])
                code[r] = new
                sums.append(total)
            out.append(np.unique(np.asarray(sums, np.float64) * scale))
        return out
