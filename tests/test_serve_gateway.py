"""STORM serving-gateway tests (DESIGN.md §10).

The contracts: (1) a tenant's counters after ANY interleaving of gateway
ticks are bit-identical to the standalone ``sketch_dataset`` build of its
stream; (2) query results are bit-identical to standalone
``ops.query_theta_with_weights`` calls against the tenant's lone sketch (the
values a ``fit`` run's loss closure computes); (3) the tick never recompiles
under any request mix (three fixed programs); (4) a 1+-device mesh splitting
tenants over the bank axis reproduces the meshless gateway bit-for-bit.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from _jaxpr import pallas_kernels  # noqa: E402
from repro.core import fleet, lsh, regression, sketch as sketch_lib  # noqa: E402
from repro.data import datasets  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve.storm_gateway import (  # noqa: E402
    IngestRequest, QueryRequest, StormGateway,
)

jax.config.update("jax_platform_name", "cpu")

S = 4
D = 5  # sketch-space dim (params hash D + 2)


@pytest.fixture(scope="module")
def params():
    return lsh.init_srp(jax.random.PRNGKey(0), 64, 3, D + 2)


def _streams(n_base=37, step=11, seed=10):
    return [
        np.asarray(0.3 * jax.random.normal(jax.random.PRNGKey(seed + t),
                                           (n_base + step * t, D)),
                   np.float32)
        for t in range(S)
    ]


def _thetas(q=9, seed=50):
    return [
        np.asarray(jax.random.normal(jax.random.PRNGKey(seed + t), (q, D)),
                   np.float32)
        for t in range(S)
    ]


class TestIngest:
    def test_interleaved_chunks_match_standalone_build(self, params):
        """Chunked, shuffled, multi-tick ingest == one-shot sketch_dataset."""
        gw = StormGateway(params, S, query_slots=4, ingest_slots=16)
        streams = _streams()
        rng = np.random.default_rng(0)
        chunks = []
        for t, z in enumerate(streams):
            for off in range(0, len(z), 13):
                chunks.append((t, z[off:off + 13]))
        rng.shuffle(chunks)
        for i, (t, z) in enumerate(chunks):
            gw.submit(IngestRequest(rid=i, tenant=t, z=z))
        gw.run_until_idle()
        for t, z in enumerate(streams):
            sk = sketch_lib.sketch_dataset(params, jnp.asarray(z), batch=16,
                                           engine="scan")
            np.testing.assert_array_equal(
                np.asarray(gw.bank.counts[t]), np.asarray(sk.counts)
            )
            assert int(gw.bank.n[t]) == len(z)

    def test_overflow_spills_to_next_tick(self, params):
        """Rows beyond a tick's capacity stay queued, in order."""
        gw = StormGateway(params, 1, query_slots=2, ingest_slots=8)
        z = _streams()[0][:20]
        gw.submit(IngestRequest(rid=0, tenant=0, z=z))
        rep = gw.tick()
        assert rep.rows_ingested == 8 and gw.pending == 1
        rep = gw.tick()
        assert rep.rows_ingested == 8
        rep = gw.tick()
        assert rep.rows_ingested == 4 and gw.pending == 0
        sk = sketch_lib.sketch_dataset(params, jnp.asarray(z), batch=8,
                                       engine="scan")
        np.testing.assert_array_equal(np.asarray(gw.bank.counts[0]),
                                      np.asarray(sk.counts))

    def test_single_sided_gateway(self, params):
        """paired=False: ingest takes PRE-AUGMENTED rows at params.dim (the
        classification contract) and queries divide by n, not 2n."""
        gw = StormGateway(params, 2, paired=False, query_slots=4,
                          ingest_slots=64)
        assert gw.ingest_dim == params.dim
        x = 0.4 * jax.random.normal(jax.random.PRNGKey(40), (30, D))
        x = np.asarray(x / jnp.maximum(
            jnp.linalg.norm(x, axis=-1, keepdims=True), 1.0))
        aug = np.asarray(lsh.augment_data(jnp.asarray(x)), np.float32)
        gw.submit(IngestRequest(rid=0, tenant=1, z=aug))
        gw.tick()
        sk = sketch_lib.sketch_dataset(params, lsh.augment_data(
            jnp.asarray(x)), batch=64, paired=False, engine="scan")
        np.testing.assert_array_equal(np.asarray(gw.bank.counts[1]),
                                      np.asarray(sk.counts))
        theta = _thetas(q=3)[0]
        gw.submit(QueryRequest(rid=1, tenant=1, thetas=theta))
        res = gw.run_until_idle()
        w = ops.from_lsh_params(params)
        want = np.asarray(ops.query_theta_with_weights(
            gw.sketch_of(1), w, jnp.asarray(theta), paired=False))
        np.testing.assert_array_equal(res[0].losses, want)

    def test_narrow_dtype_gateway_saturates(self, params):
        """A narrow-counter gateway pins at the dtype max, never wraps."""
        p2 = lsh.init_srp(jax.random.PRNGKey(3), 4, 1, 4)
        gw = StormGateway(p2, 1, query_slots=2, ingest_slots=64,
                          count_dtype=jnp.int8)
        z = np.asarray(0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                               (400, 2)), np.float32)
        for off in range(0, 400, 64):
            gw.submit(IngestRequest(rid=off, tenant=0, z=z[off:off + 64]))
        gw.run_until_idle()
        assert gw.bank.counts.dtype == jnp.int8
        assert int(jnp.max(gw.bank.counts)) == 127
        sk = sketch_lib.sketch_dataset(p2, jnp.asarray(z), batch=64,
                                       dtype=jnp.int8, engine="scan")
        np.testing.assert_array_equal(np.asarray(gw.bank.counts[0]),
                                      np.asarray(sk.counts))


class TestQuery:
    def test_results_match_standalone_query(self, params):
        """Gateway answers == lone-sketch ops.query_theta_with_weights."""
        gw = StormGateway(params, S, query_slots=4, ingest_slots=64)
        streams = _streams()
        for t, z in enumerate(streams):
            gw.submit(IngestRequest(rid=t, tenant=t, z=z))
        while gw.pending:
            gw.tick()
        thetas = _thetas()
        for t in range(S):
            gw.submit(QueryRequest(rid=t, tenant=t, thetas=thetas[t]))
        results = {r.rid: r for r in gw.run_until_idle()}
        w = ops.from_lsh_params(params)
        for t in range(S):
            want = np.asarray(ops.query_theta_with_weights(
                gw.sketch_of(t), w, jnp.asarray(thetas[t]), paired=True
            ))
            np.testing.assert_array_equal(results[t].losses, want)
            assert results[t].tenant == t

    def test_results_match_fit_loss_closure(self, params):
        """The gateway serves what a fit run's loss closure computes
        (fleet.make_loss_fn on the tenant's sketch) for a candidate fleet.

        The scan-engine closure is a *different compiled program* (einsum
        hashing, its own jit) than the gateway tick, so agreement is to fp
        tolerance only — the DESIGN.md §9 cross-program caveat. Bit-level
        identity against the same-program ``ops`` path is pinned in
        ``test_results_match_standalone_query``.
        """
        gw = StormGateway(params, S, query_slots=8, ingest_slots=64)
        streams = _streams()
        for t, z in enumerate(streams):
            gw.submit(IngestRequest(rid=t, tenant=t, z=z))
        while gw.pending:
            gw.tick()
        cand = _thetas(q=6, seed=70)
        for t in range(S):
            gw.submit(QueryRequest(rid=t, tenant=t, thetas=cand[t]))
        results = {r.rid: r for r in gw.run_until_idle()}
        for t in range(S):
            loss_fn = fleet.make_loss_fn(gw.sketch_of(t), params,
                                         paired=True, engine="scan",
                                         d=D - 1)
            want = np.asarray(loss_fn(jnp.asarray(cand[t])))
            np.testing.assert_allclose(results[t].losses, want, rtol=1e-5)

    def test_read_your_writes_within_tick(self, params):
        """A mixed tick applies ingest first; queries see the new rows."""
        gw = StormGateway(params, 1, query_slots=2, ingest_slots=64)
        z = _streams()[0]
        theta = _thetas(q=1)[0]
        gw.submit(IngestRequest(rid=0, tenant=0, z=z))
        gw.submit(QueryRequest(rid=1, tenant=0, thetas=theta))
        rep = gw.tick()
        assert rep.rows_ingested == len(z) and len(rep.results) == 1
        w = ops.from_lsh_params(params)
        want = np.asarray(ops.query_theta_with_weights(
            gw.sketch_of(0), w, jnp.asarray(theta), paired=True
        ))
        np.testing.assert_array_equal(rep.results[0].losses, want)

    def test_split_request_reassembles(self, params):
        """A request larger than a tick's slots spans ticks and reports once,
        with rows in submission order."""
        gw = StormGateway(params, 1, query_slots=3, ingest_slots=4)
        z = _streams()[0]
        gw.submit(IngestRequest(rid=0, tenant=0, z=z[:16]))
        while gw.pending:
            gw.tick()
        thetas = _thetas(q=10)[0]
        gw.submit(QueryRequest(rid=7, tenant=0, thetas=thetas))
        reports = [gw.tick() for _ in range(4)]
        done = [r for rep in reports for r in rep.results]
        assert len(done) == 1 and done[0].rid == 7
        assert [rep.points_served for rep in reports] == [3, 3, 3, 1]
        w = ops.from_lsh_params(params)
        want = np.asarray(ops.query_theta_with_weights(
            gw.sketch_of(0), w, jnp.asarray(thetas), paired=True
        ))
        np.testing.assert_array_equal(done[0].losses, want)


class TestQueryPath:
    """Tenant-major slots engage the routed-fetch query kernel (DESIGN.md
    §10.2); the gateway records the path each program compiled with."""

    @pytest.mark.parametrize("q,path,kernel", [
        (16, "tenant_major", "_query_kernel_tenant_major"),
        (12, "one_hot", "_query_kernel"),
    ])
    def test_programs_record_path_and_answers_stay_bit_equal(
            self, params, q, path, kernel):
        gw = StormGateway(params, S, query_slots=q, ingest_slots=64,
                          mode="interpret")
        for t, z in enumerate(_streams()):
            gw.submit(IngestRequest(rid=t, tenant=t, z=z))
        while gw.pending:
            gw.tick()
        thetas = _thetas(q=q + 3)  # spills into a second query tick
        for t in range(S):
            gw.submit(QueryRequest(rid=t, tenant=t, thetas=thetas[t]))
        results = {r.rid: r for r in gw.run_until_idle()}
        assert gw.query_paths == {"tick_query": path}
        flat = jnp.zeros((S * q * (D + 1),), jnp.float32)
        program = jax.make_jaxpr(gw._tick_query)(gw.bank.counts, gw.bank.n,
                                                 flat)
        assert pallas_kernels(program) == [kernel]
        w = ops.from_lsh_params(params)
        for t in range(S):
            want = np.asarray(ops.query_theta_with_weights(
                gw.sketch_of(t), w, jnp.asarray(thetas[t]), paired=True,
                mode="interpret"))
            np.testing.assert_array_equal(results[t].losses, want)

    def test_fleet_closure_keeps_one_hot(self, params, monkeypatch):
        """A banked loss closure routes by an arbitrary member map, so on
        the chip it runs the one-hot kernel. Traced, not run, with the
        backend check steered to the chip's branch."""
        bank = sketch_lib.bank_of([sketch_lib.Sketch(
            counts=jnp.zeros((params.rows, params.buckets), jnp.int32),
            n=jnp.int32(1))] * 3)
        jax.clear_caches()  # no cached CPU trace stands in for the chip's
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        try:
            loss = fleet.make_loss_fn(bank, params, engine="kernel",
                                      member_map=jnp.array([2, 0, 1]))
            program = jax.make_jaxpr(loss)(jnp.ones((6, D)))
            assert ops.query_path("auto", params.dim) == "one_hot"
        finally:
            monkeypatch.undo()
            jax.clear_caches()
        assert pallas_kernels(program) == ["_query_kernel"]


class TestEngineDiscipline:
    def test_never_recompiles_across_mixes(self, params):
        """Any request mix rides exactly three fixed programs."""
        gw = StormGateway(params, S, query_slots=4, ingest_slots=8)
        streams = _streams()
        thetas = _thetas(q=3)
        rng = np.random.default_rng(1)
        rid = 0
        for round_ in range(6):
            for t in range(S):
                if rng.random() < 0.7:
                    off = rng.integers(0, 20)
                    gw.submit(IngestRequest(rid=rid, tenant=t,
                                            z=streams[t][off:off + 7]))
                    rid += 1
                if rng.random() < 0.7:
                    gw.submit(QueryRequest(rid=rid, tenant=t,
                                           thetas=thetas[t]))
                    rid += 1
            gw.tick()
        gw.run_until_idle()
        rep = gw.tick()  # idle tick: host-side no-op, still counted
        assert rep.results == [] and rep.rows_ingested == 0
        assert gw.trace_count <= 3

    def test_zero_row_query_completes(self, params):
        """A (0, dim) query request completes (empty losses) instead of
        wedging run_until_idle."""
        gw = StormGateway(params, S, query_slots=2, ingest_slots=4)
        gw.submit(QueryRequest(rid=9, tenant=0,
                               thetas=np.zeros((0, D), np.float32)))
        res = gw.run_until_idle()
        assert len(res) == 1 and res[0].rid == 9
        assert res[0].losses.shape == (0,)

    def test_validation(self, params):
        gw = StormGateway(params, S, query_slots=2, ingest_slots=4)
        with pytest.raises(ValueError, match="tenant"):
            gw.submit(IngestRequest(rid=0, tenant=S, z=np.zeros((2, D))))
        with pytest.raises(ValueError, match="ingest rows"):
            gw.submit(IngestRequest(rid=0, tenant=0, z=np.zeros((2, D + 1))))
        with pytest.raises(ValueError, match="query thetas"):
            gw.submit(QueryRequest(rid=0, tenant=0, thetas=np.zeros((2, 3))))
        with pytest.raises(ValueError, match="bank holds"):
            StormGateway(params, S, bank=sketch_lib.SketchBank(
                counts=jnp.zeros((S + 1, 64, 8), jnp.int32),
                n=jnp.zeros((S + 1,), jnp.int32),
            ))

    def test_warm_start_bank(self, params):
        """A gateway over a prebuilt bank serves it unchanged."""
        streams = _streams()
        bank = sketch_lib.sketch_dataset_many(
            params, [jnp.asarray(z) for z in streams], batch=16,
            engine="scan")
        gw = StormGateway(params, S, query_slots=4, ingest_slots=4,
                          bank=bank)
        np.testing.assert_array_equal(np.asarray(gw.bank.counts),
                                      np.asarray(bank.counts))
        theta = _thetas(q=2)
        gw.submit(QueryRequest(rid=0, tenant=2, thetas=theta[2]))
        res = gw.run_until_idle()
        w = ops.from_lsh_params(params)
        want = np.asarray(ops.query_theta_with_weights(
            bank.select(2), w, jnp.asarray(theta[2]), paired=True
        ))
        np.testing.assert_array_equal(res[0].losses, want)


class TestShardedGateway:
    def test_mesh_matches_meshless_bit_for_bit(self, params):
        """Tenants split over a 2-device bank axis: same counters, same
        answers as the meshless gateway."""
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 host devices")
        streams = _streams()
        thetas = _thetas(q=3)

        def run(mesh):
            gw = StormGateway(params, S, query_slots=4, ingest_slots=16,
                              mesh=mesh)
            for t, z in enumerate(streams):
                gw.submit(IngestRequest(rid=t, tenant=t, z=z))
                gw.submit(QueryRequest(rid=100 + t, tenant=t,
                                       thetas=thetas[t]))
            res = {r.rid: r.losses for r in gw.run_until_idle()}
            return gw, res

        gw0, r0 = run(None)
        mesh = Mesh(np.array(jax.devices()[:2]), ("bank",))
        gw1, r1 = run(mesh)
        np.testing.assert_array_equal(np.asarray(gw0.bank.counts),
                                      np.asarray(gw1.bank.counts))
        np.testing.assert_array_equal(np.asarray(gw0.bank.n),
                                      np.asarray(gw1.bank.n))
        for rid in r0:
            np.testing.assert_array_equal(r0[rid], r1[rid])

    def test_indivisible_bank_rejected(self, params):
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 host devices")
        mesh = Mesh(np.array(jax.devices()[:2]), ("bank",))
        with pytest.raises(ValueError, match="divisible"):
            StormGateway(params, 3, mesh=mesh)


class TestEndToEnd:
    def test_served_sketch_trains_like_offline_sketch(self, params):
        """regression.fit(prebuilt=<served sketch>) == fit(prebuilt=<offline
        sketch>) — the gateway's counters are the real training artifact."""
        x, y, _ = datasets.make_regression(jax.random.PRNGKey(1), 256, D - 1,
                                           noise=0.2, condition=3)
        cfg = regression.StormRegressorConfig(
            rows=64, planes=3, batch=64, engine="scan",
        )
        xs = (x - x.mean(0)) / (x.std(0) + 1e-8)
        ys = (y - y.mean()) / (y.std() + 1e-8)
        z, _ = lsh.scale_to_unit_ball(
            jnp.concatenate([xs, ys[:, None]], axis=-1), cfg.norm_slack
        )
        gw = StormGateway(params, S, query_slots=4, ingest_slots=64)
        z_np = np.asarray(z)
        for off in range(0, len(z_np), 50):
            gw.submit(IngestRequest(rid=off, tenant=1, z=z_np[off:off + 50]))
        gw.run_until_idle()
        offline = sketch_lib.sketch_dataset(params, z, batch=cfg.batch,
                                            engine="scan")
        fit_served = regression.fit(jax.random.PRNGKey(2), x, y, cfg,
                                    prebuilt=(gw.sketch_of(1), params, None))
        fit_offline = regression.fit(jax.random.PRNGKey(2), x, y, cfg,
                                     prebuilt=(offline, params, None))
        np.testing.assert_array_equal(np.asarray(fit_served.theta),
                                      np.asarray(fit_offline.theta))
        np.testing.assert_array_equal(np.asarray(fit_served.losses),
                                      np.asarray(fit_offline.losses))
