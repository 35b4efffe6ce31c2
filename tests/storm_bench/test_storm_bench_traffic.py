"""The traffic a cell sends is fixed by its seed, and every seed gets the
same multiset of work in another order."""

import numpy as np
import pytest

import _bench_path  # noqa: F401
from storm_bench import cells, payloads
from storm_bench.serve import INGEST, QUERY

SEED = 3_000_000_019  # above 2**31, as the driver's seeds are


def _open_loop(seed, name="read-zipf", seconds=10.0):
    config = cells.load_json(cells.HERE / "configs" / "parkinsons-r2048.json")
    spec = cells.load_json(cells.HERE / "traffic" / f"{name}.json")
    return cells.generator("open_loop").Driver(spec, config, seed, seconds)


def test_open_loop_schedule_is_deterministic_per_seed():
    a, b, c = _open_loop(SEED), _open_loop(SEED), _open_loop(SEED + 1)
    for field in ("due", "kind", "tenant", "block"):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
    assert not np.array_equal(a.tenant, c.tenant)
    assert not np.array_equal(a.due, c.due)


def test_every_seed_gets_the_same_work():
    a, b = _open_loop(SEED), _open_loop(12)
    assert a.n_window == b.n_window == round(a.rate * 10.0)
    w = slice(0, a.n_window)
    np.testing.assert_allclose(np.sort(np.diff(a.due[w], prepend=0)),
                               np.sort(np.diff(b.due[w], prepend=0)))
    assert (a.kind[w] == INGEST).sum() == (b.kind[w] == INGEST).sum()
    assert (a.kind[w] == QUERY).sum() == round(0.95 * a.n_window)
    ranks = [np.sort(np.bincount(d.tenant[w], minlength=1024))
             for d in (a, b)]
    np.testing.assert_array_equal(*ranks)


def test_zipf_draw_follows_the_law_and_is_seeded():
    draws = payloads.tenant_draws(payloads.rng_of(SEED, 0), 100_000, 1024,
                                  0.99)
    again = payloads.tenant_draws(payloads.rng_of(SEED, 0), 100_000, 1024,
                                  0.99)
    np.testing.assert_array_equal(draws, again)
    counts = np.sort(np.bincount(draws, minlength=1024))[::-1]
    weights = np.arange(1, 1025) ** -0.99
    np.testing.assert_allclose(counts, 100_000 * weights / weights.sum(),
                               atol=1.0)
    hot = np.bincount(draws).argmax()
    other = payloads.tenant_draws(payloads.rng_of(SEED + 1, 0), 100_000,
                                  1024, 0.99)
    assert np.bincount(other, minlength=1024)[hot] < counts[0]


def test_uniform_draw_is_exactly_even():
    draws = payloads.tenant_draws(payloads.rng_of(1, 0), 4096, 1024, 0.0)
    assert set(np.bincount(draws, minlength=1024)) == {4}


@pytest.mark.parametrize("stream", [1, 2])
def test_payload_pools_are_seeded(stream):
    make = {1: lambda r: payloads.row_pool(r, 21, 64, 0.4, 50.0),
            2: lambda r: payloads.theta_pool(r, 22, 8)}[stream]
    a, b = make(payloads.rng_of(SEED, stream)), make(payloads.rng_of(SEED,
                                                                     stream))
    np.testing.assert_array_equal(a, b)
    if stream == 1:
        assert a.shape == (payloads.POOL_BLOCKS, 64, 22)
        assert np.linalg.norm(a, axis=-1).max() <= 0.99 + 1e-6


@pytest.mark.parametrize("name,kind", [("airfoil.load-uniform", INGEST),
                                       ("parkinsons.read-flood", QUERY)])
def test_closed_loop_rows_do_not_depend_on_timing(name, kind):
    cell = cells.load_cell(name)
    a = cells.generator("closed_loop").Driver(cell.traffic, cell.config,
                                              SEED, 10.0)
    b = cells.generator("closed_loop").Driver(cell.traffic, cell.config,
                                              SEED, 10.0)
    np.testing.assert_array_equal(a.a, b.a)
    np.testing.assert_array_equal(a.b, b.b)
    assert np.all(a.b % 2 == 1)  # odd strides visit every pool block
    assert a.kind == kind and a.programs == {"ingest" if kind == INGEST
                                             else "query"}
