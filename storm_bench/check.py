"""Whether the timed path produced the right answers: ``correct``.

After the window closes, a seeded sample of what the window served is
compared with ``reference.py``:

* ``unanswered``: requests due in the window that failed or never completed
  (limit 0);
* ``n_off``: tenants whose row count ``n`` differs from the rows the harness
  sent them (every tenant; limit 0);
* ``mass_off``: tenants with a hash row whose counts do not sum to twice the
  rows sent (every tenant; limit 0), which catches a row dropped anywhere;
* ``counts_moved_share``: of the count increments compared, the share placed
  in another bucket than the reference places them. Compared are every hash
  row of each tenant the query sample reads, and ``COUNT_ROWS`` hash rows
  spread evenly over ``R`` of ``COUNT_TENANTS`` tenants spread evenly over
  the bank (a seeded offset, then a fixed stride), so that a fault confined
  to a block of tenants or of hash rows is seen;
* ``query_off_share``: of the points of the window's query requests (a
  seeded sample of ``QUERY_REQUESTS`` where there are more), the share
  whose answer is none of the answers the reference allows, to
  ``QUERY_RTOL`` relative. The reference allows the answers that the
  query's pad, which float32 rounding leaves anywhere in
  ``[0, reference.PAD_MAX]``, can give (``Reference.query_paths``). It
  answers from the rows the tenant had acknowledged by the tick that
  answered the query (read-your-writes); a query whose tenant had rows
  half-way through the gateway at that tick is left out. So is a query of
  a tenant whose counters differ from the reference's at the end of the
  run: an increment placed within rounding of a hyperplane moves its
  answers by a count, and ``counts_moved_share`` judges that already.

A sound run differs from the reference only where a projection lies within
rounding of zero, so the limits of the two shares (``limits.json``) sit
between what sound runs read and what the control reads (``PERF.md``).
"""

from __future__ import annotations

import numpy as np

from storm_bench import payloads
from storm_bench.cells import HERE, load_json
from storm_bench.reference import Reference
from storm_bench.serve import INGEST, QUERY, WINDOW

COUNT_TENANTS = 64
COUNT_ROWS = 64
QUERY_REQUESTS = 4096
QUERY_RTOL = 1e-6
LIMITS = load_json(HERE / "limits.json")


def _spread(rng, total: int, k: int) -> np.ndarray:
    """``k`` of ``range(total)`` at a fixed stride from a seeded offset."""
    if total <= k:
        return np.arange(total)
    stride = total // k
    return rng.integers(stride) + stride * np.arange(k)


def sample(log: dict, tenants: int, hash_rows: int, seed: int):
    """The tenants and hash rows whose counters are compared, and the query
    rids."""
    rng = payloads.rng_of(seed, 7)
    counted = _spread(rng, tenants, COUNT_TENANTS)
    rows = _spread(rng, hash_rows, COUNT_ROWS)
    q = np.flatnonzero((log["kind"] == QUERY) & (log["phase"] == WINDOW)
                       & np.isfinite(log["done"]) & ~log["failed"])
    rids = np.sort(rng.choice(q, min(QUERY_REQUESTS, len(q)), replace=False))
    return counted, rows, rids


def rows_sent(log: dict, tenants: int, rows_per: int) -> np.ndarray:
    ok = (log["kind"] == INGEST) & ~log["failed"]
    return np.bincount(log["tenant"][ok], minlength=tenants) * rows_per


def program_state(gw, tenants_read, sent: np.ndarray) -> dict:
    """Host copies of what the program holds, before its state is freed."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def mass_ok(counts, sent):
        return jnp.all(jnp.sum(counts.astype(jnp.int32), axis=2)
                       == 2 * sent[:, None], axis=1)

    counts = gw.bank.counts
    idx = np.asarray(sorted(tenants_read), np.int32)
    tables = np.asarray(counts[jnp.asarray(idx)]).astype(np.int64)
    return {"n": np.asarray(gw.bank.n),
            "mass_ok": np.asarray(mass_ok(counts, jnp.asarray(sent,
                                                             jnp.int32))),
            "tables": dict(zip(idx.tolist(), tables))}


def replay(ref: Reference, log: dict, rows: np.ndarray, thetas: np.ndarray,
           counted, hash_rows, rids: np.ndarray, tick_starts: np.ndarray,
           paths: bool = True):
    """The reference's final tables and its answers to query ``rids``.

    Tables: ``{tenant: (hash rows or None for all, counts)}``, every hash
    row for the tenants the queries read, ``hash_rows`` for the other
    ``counted`` tenants. Answers: ``{rid: [allowed answers per point]}``
    with ``paths``, else ``{rid: answers}`` (the control's own); rids left
    out as ambiguous are not in them."""
    ingest = np.flatnonzero((log["kind"] == INGEST) & ~log["failed"]
                            & np.isfinite(log["done"]))
    queried = set(log["tenant"][rids].tolist())
    tables, answers = {}, {}
    for t in sorted(queried | set(np.asarray(counted).tolist())):
        sel = None if t in queried else hash_rows
        mine = ingest[log["tenant"][ingest] == t]
        mine = mine[np.argsort(log["tick"][mine], kind="stable")]
        qs = rids[log["tenant"][rids] == t]
        qs = qs[np.argsort(log["tick"][qs], kind="stable")]
        table, n, j = ref.empty(sel), 0, 0
        for rid in qs:
            k = log["tick"][rid]
            upto = j + np.searchsorted(log["tick"][mine[j:]], k, "right")
            if upto > j:
                z = rows[log["block"][mine[j:upto]]].reshape(
                    -1, rows.shape[-1])
                ref.insert(table, z, sel)
                n += len(z)
                j = upto
            started = tick_starts[k - 1]
            if np.any(log["sent"][mine[j:]] < started):
                continue  # rows of this tenant were in the gateway mid-way
            theta = thetas[log["block"][rid]]
            answers[rid] = (ref.query_paths(table, n, theta) if paths
                            else ref.query(table, n, theta))
        if j < len(mine):
            ref.insert(table, rows[log["block"][mine[j:]]].reshape(
                -1, rows.shape[-1]), sel)
        tables[t] = (sel, table)
    return tables, answers


def compare(expected, got: dict, sent: np.ndarray, unanswered: int,
            tenant: np.ndarray) -> dict:
    """Each number compared, beside its limit; ``tenant`` maps a rid to
    its tenant."""
    tables, answers = expected
    moved = total = 0
    exact = set()
    for t, (sel, table) in tables.items():
        mine = got["tables"][t] if sel is None else got["tables"][t][sel]
        diff = int(np.abs(mine - table).sum()) // 2
        moved += diff
        total += 2 * int(sent[t]) * len(table)
        if sel is None and not diff:
            exact.add(t)
    off = points = 0
    for rid, allowed in answers.items():
        if tenant[rid] not in exact:
            continue
        for served, ok in zip(got["answers"][rid], allowed):
            points += 1
            off += not np.any(np.abs(served - ok) <= QUERY_RTOL * np.abs(ok))
    numbers = {
        "unanswered": unanswered,
        "n_off": int(np.sum(got["n"] != sent)),
        "mass_off": int(np.sum(~got["mass_ok"])),
        "counts_moved_share": moved / max(total, 1),
    }
    if points:
        numbers["query_off_share"] = off / points
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}


def control_state(tables: dict, answers: dict, sent: np.ndarray) -> dict:
    """The control in the program's place: its tables and answers, with
    exact row counts and masses. Tables of sampled hash rows are compared
    as they are; a full one stands for the tenant's whole table."""
    mass_ok = np.ones(len(sent), bool)
    full = {}
    for t, (sel, table) in tables.items():
        mass_ok[t] = np.all(table.sum(1) == 2 * sent[t])
        if sel is None:
            full[t] = table
        else:
            whole = np.zeros((int(np.max(sel)) + 1, table.shape[1]),
                             table.dtype)
            whole[sel] = table
            full[t] = whole
    return {"n": sent.copy(), "mass_ok": mass_ok, "tables": full,
            "answers": answers}


def correct(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())
