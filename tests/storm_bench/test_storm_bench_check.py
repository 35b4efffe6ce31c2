"""The comparison that decides ``correct``: which query answers the
reference allows, and which counters it reads."""

import numpy as np

import _bench_path  # noqa: F401
from storm_bench import check, payloads
from storm_bench.reference import PAD_MAX, Reference

SEED = 3_000_000_031


def _sketch(rows=512, planes=4, dim=8, n=2048):
    rng = payloads.rng_of(SEED, 0)
    w = rng.normal(size=(rows, planes, dim + 2)).astype(np.float32)
    ref = Reference(w)
    data = payloads.row_pool(rng, dim - 1, 64, 0.3, 10.0, blocks=n // 64)
    table = ref.empty()
    ref.insert(table, data.reshape(-1, dim))
    thetas = payloads.theta_pool(rng, dim, 256, blocks=1)[0]
    return rng, w, table, n, thetas


def _answers(w, table, n, thetas, pad, flip=None):
    """Answers computed in float64 with a given pad for every query, and
    optionally one projection of each query's row 0 forced to the other
    sign."""
    q = thetas / np.linalg.norm(thetas, axis=1, keepdims=True)
    aug = np.concatenate([q, pad[:, None], np.zeros((len(q), 1))], 1)
    proj = np.einsum("md,rpd->mrp", aug, w.astype(np.float64))
    bits = proj > 0
    if flip is not None:
        bits[:, 0, flip] = ~bits[:, 0, flip]
    codes = (bits << np.arange(w.shape[1])).sum(2)
    hits = table[np.arange(w.shape[0])[None], codes]
    return hits.mean(1) / (2.0 * n)


def test_any_pad_in_range_gives_an_allowed_answer():
    rng, w, table, n, thetas = _sketch()
    allowed = Reference(w).query_paths(table, n, thetas)
    assert max(len(a) for a in allowed) > 1  # the pad does move some codes
    for pad in (np.zeros(len(thetas)), rng.uniform(0, PAD_MAX, len(thetas)),
                np.full(len(thetas), PAD_MAX * 0.999)):
        got = _answers(w, table, n, thetas, pad)
        for g, ok in zip(got, allowed):
            assert np.any(np.abs(g - ok) <= check.QUERY_RTOL * np.abs(ok))


def test_a_sign_flip_off_the_pad_path_is_not_allowed():
    _, w, table, n, thetas = _sketch()
    allowed = Reference(w).query_paths(table, n, thetas)
    base = _answers(w, table, n, thetas, np.zeros(len(thetas)))
    got = _answers(w, table, n, thetas, np.zeros(len(thetas)), flip=0)
    off = np.asarray([not np.any(np.abs(g - ok) <= check.QUERY_RTOL
                                 * np.abs(ok))
                      for g, ok in zip(got, allowed)])
    # a flip between two buckets of equal count (often both empty) leaves
    # the answer as it was, and a few moved answers land on one that the
    # pad allows; the rest are caught
    moved = np.abs(got - base) > check.QUERY_RTOL * np.abs(base)
    assert moved.mean() > 0.5 and off[moved].mean() > 0.9


def test_counted_tenants_and_rows_are_spread_over_the_bank():
    log = {k: np.zeros(0) for k in ("kind", "phase", "done", "failed")}
    log["failed"] = log["failed"].astype(bool)
    tenants, rows, rids = check.sample(log, 1024, 512, SEED)
    assert len(tenants) == check.COUNT_TENANTS and len(rids) == 0
    assert len(rows) == check.COUNT_ROWS
    # one in every block of 1024 / 64 tenants and of 512 / 64 hash rows
    assert np.all(np.diff(tenants) == 1024 // check.COUNT_TENANTS)
    assert np.all(np.bincount(tenants // 16, minlength=64) == 1)
    assert np.all(np.bincount(rows // 8, minlength=64) == 1)
    again, _, _ = check.sample(log, 1024, 512, SEED)
    assert np.array_equal(tenants, again)
    small, small_rows, _ = check.sample(log, 4, 32, SEED)
    assert list(small) == [0, 1, 2, 3] and len(small_rows) == 32


def test_sampled_hash_rows_count_as_the_full_table_does():
    rng = payloads.rng_of(SEED, 1)
    w = rng.normal(size=(64, 4, 10)).astype(np.float32)
    ref = Reference(w)
    z = payloads.row_pool(rng, 7, 64, 0.3, 10.0, blocks=4).reshape(-1, 8)
    full, part = ref.empty(), ref.empty(np.arange(3, 64, 8))
    ref.insert(full, z)
    ref.insert(part, z, np.arange(3, 64, 8))
    assert np.array_equal(full[3::8], part)
