"""Fleet-vectorized optimization tests (DESIGN.md §8).

Pins the three contracts of the fleet refactor:

* **Equivalence** — ``minimize_fleet`` with stacked seeds matches a Python
  loop of ``minimize`` calls bit-for-bit on the ref path; fleet
  ``quadratic_refine`` equals a ``jax.vmap`` of the single; ``fleet_fit`` on
  a 1-device mesh equals the unsharded run.
* **Query batching** — one fused loss call of ``F*(2k+1)`` points per DFO
  step for the whole fleet (trace-count + jaxpr gather-count).
* **Hoisted weights** — no ``(R, p, d) -> (p, d, R)`` transpose inside the
  scanned DFO step (jaxpr-level, against the session-hoisted loss closure).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from _jaxpr import all_eqns

from repro.core import dfo, distributed, lsh, regression, sketch as sketch_lib

jax.config.update("jax_platform_name", "cpu")


def _sketch_problem(d=4, rows=64, seed=0):
    kz, kp = jax.random.split(jax.random.PRNGKey(seed))
    z = 0.5 * jax.random.normal(kz, (200, d))
    zs, _ = lsh.scale_to_unit_ball(z)
    params = lsh.init_srp(kp, rows, 3, d + 2)
    sk = sketch_lib.sketch_dataset(params, zs, batch=50, paired=True)
    loss = jax.jit(
        lambda th: sketch_lib.query_theta(sk, params, th, paired=True)
    )
    return sk, params, loss


def _fleet_cfg(**kw):
    base = dict(steps=25, num_queries=4, sigma=0.4, sigma_decay=0.99,
                learning_rate=0.5, decay=0.99, average_tail=0.4)
    base.update(kw)
    return dfo.DFOConfig(**base)


class TestMinimizeFleetEquivalence:
    def test_matches_loop_of_minimize_bit_for_bit(self):
        """F stacked seeds advance exactly like F independent minimize calls
        — the fused F*(2k+1) query batch changes the schedule, not one bit of
        the math (ref sketch-query path)."""
        _, _, loss = _sketch_problem()
        cfg = _fleet_cfg()
        f = 3
        keys = jax.random.split(jax.random.PRNGKey(7), f)
        theta0 = jnp.stack(
            [jnp.zeros(4), 0.1 * jnp.ones(4), -0.2 * jnp.ones(4)]
        )
        proj = dfo.pin_last_coordinate(-1.0)

        fleet = dfo.minimize_fleet(loss, theta0, keys, cfg, project=proj)
        loop = [dfo.minimize(loss, theta0[i], keys[i], cfg, project=proj)
                for i in range(f)]
        np.testing.assert_array_equal(
            np.asarray(fleet.theta), np.asarray(jnp.stack([r.theta for r in loop]))
        )
        np.testing.assert_array_equal(
            np.asarray(fleet.losses),
            np.asarray(jnp.stack([r.losses for r in loop])),
        )

    def test_per_member_hyperparameters_match_loop(self):
        """The σ/lr diversity ladder equals a loop with per-member configs."""
        _, _, loss = _sketch_problem(seed=1)
        cfg = _fleet_cfg()
        f = 3
        keys = jax.random.split(jax.random.PRNGKey(9), f)
        theta0 = jnp.zeros((f, 4))
        sig = jnp.asarray([0.3, 0.5, 0.8])
        lr = jnp.asarray([0.2, 0.5, 1.0])
        fleet = dfo.minimize_fleet(loss, theta0, keys, cfg,
                                   sigma=sig, learning_rate=lr)
        loop = jnp.stack([
            dfo.minimize(
                loss, theta0[i], keys[i],
                dataclasses.replace(cfg, sigma=float(sig[i]),
                                    learning_rate=float(lr[i])),
            ).theta
            for i in range(f)
        ])
        np.testing.assert_array_equal(np.asarray(fleet.theta), np.asarray(loop))

    def test_non_antithetic_fleet_matches_loop(self):
        _, _, loss = _sketch_problem(seed=2)
        cfg = _fleet_cfg(antithetic=False, num_queries=6)
        keys = jax.random.split(jax.random.PRNGKey(3), 2)
        theta0 = jnp.zeros((2, 4))
        fleet = dfo.minimize_fleet(loss, theta0, keys, cfg)
        loop = jnp.stack(
            [dfo.minimize(loss, theta0[i], keys[i], cfg).theta for i in range(2)]
        )
        np.testing.assert_array_equal(np.asarray(fleet.theta), np.asarray(loop))

    def test_shapes_and_projection(self):
        _, _, loss = _sketch_problem(seed=3)
        cfg = _fleet_cfg(steps=13)
        f = 5
        res = dfo.minimize_fleet(
            loss, 0.1 * jnp.ones((f, 4)),
            jax.random.split(jax.random.PRNGKey(0), f), cfg,
            project=dfo.pin_last_coordinate(-1.0),
        )
        assert res.theta.shape == (f, 4)
        assert res.losses.shape == (f, 13)
        np.testing.assert_array_equal(np.asarray(res.theta[:, -1]),
                                      -np.ones(f, np.float32))

    def test_bad_hyperparam_shape_raises(self):
        _, _, loss = _sketch_problem(seed=3)
        try:
            dfo.minimize_fleet(loss, jnp.zeros((3, 4)),
                               jax.random.split(jax.random.PRNGKey(0), 3),
                               _fleet_cfg(), sigma=jnp.ones(2))
            assert False, "expected ValueError"
        except ValueError:
            pass


class TestQuadraticRefineFleet:
    def test_equals_vmapped_single(self):
        loss = lambda pts: jnp.sum((pts - 0.3) ** 2, axis=-1)
        theta = jnp.stack([jnp.zeros(3), 0.5 * jnp.ones(3)])
        keys = jax.random.split(jax.random.PRNGKey(3), 2)
        fleet = dfo.quadratic_refine_fleet(loss, theta, keys, radius=0.4)
        vmapped = jax.vmap(
            lambda t, k: dfo.quadratic_refine(loss, t, k, radius=0.4)
        )(theta, keys)
        np.testing.assert_array_equal(np.asarray(fleet), np.asarray(vmapped))

    def test_respects_projection_per_member(self):
        loss = lambda pts: jnp.sum((pts - 0.2) ** 2, axis=-1)
        theta = jnp.zeros((3, 3)).at[..., -1].set(-1.0)
        out = dfo.quadratic_refine_fleet(
            loss, theta, jax.random.split(jax.random.PRNGKey(1), 3),
            radius=0.3, project=dfo.pin_last_coordinate(-1.0),
        )
        np.testing.assert_array_equal(np.asarray(out[:, -1]), -np.ones(3))


class TestFleetQueryBatching:
    """The acceptance contract: ONE fused loss call of F*(2k+1) points per
    DFO step for the whole fleet."""

    def _traced_batches(self, f, k, antithetic=True):
        batches = []

        def loss(pts):
            batches.append(pts.shape[0])
            return jnp.sum((pts - 0.5) ** 2, axis=-1)

        cfg = _fleet_cfg(steps=4, num_queries=k, antithetic=antithetic)
        dfo.minimize_fleet(loss, jnp.zeros((f, 3)),
                           jax.random.split(jax.random.PRNGKey(0), f), cfg)
        return batches

    def test_single_fused_call_per_step(self):
        """The scanned step traces the loss exactly once, on the full-fleet
        F*(2k+1) block — not per member, not per side."""
        batches = self._traced_batches(f=6, k=5)
        assert batches == [6 * (2 * 5 + 1)]

    def test_one_sided_fused_call(self):
        batches = self._traced_batches(f=4, k=3, antithetic=False)
        assert batches == [4 * (3 + 1)]

    def test_refine_two_fused_calls(self):
        """Fleet refine: one F*m trust-region call + one 2F accept call."""
        batches = []

        def loss(pts):
            batches.append(pts.shape[0])
            return jnp.sum(pts * pts, axis=-1)

        dfo.quadratic_refine_fleet(
            loss, jnp.zeros((5, 3)),
            jax.random.split(jax.random.PRNGKey(0), 5),
            radius=0.3, num_samples=20,
        )
        assert batches == [5 * 20, 2 * 5]

    def test_one_gather_per_step_in_jaxpr(self):
        """jaxpr-level proof: the scanned step contains exactly ONE gather
        against the (R, B) counter table — one sketch query serves the fleet."""
        sk, params, _ = _sketch_problem(d=4, rows=48)
        loss = regression.make_loss_fn(sk, params, engine="scan")
        cfg = _fleet_cfg(steps=6)
        f = 4
        keys = jax.random.split(jax.random.PRNGKey(0), f)
        jaxpr = jax.make_jaxpr(
            lambda th, ks: dfo.minimize_fleet(loss, th, ks, cfg).theta
        )(jnp.zeros((f, 4)), keys)
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1
        counter_shape = tuple(sk.counts.shape)
        gathers = [
            e for e in all_eqns(scans[0].params["jaxpr"].jaxpr)
            if e.primitive.name == "gather"
            and tuple(e.invars[0].aval.shape) == counter_shape
        ]
        assert len(gathers) == 1, f"expected 1 counter gather, got {len(gathers)}"


class TestHoistedWeights:
    """Satellite: the (R, p, d) -> (p, d, R) kernel-layout transpose runs
    once per fit/serve session, never inside the scanned DFO step."""

    def _scan_body_transposes(self, loss, params, f=3):
        cfg = _fleet_cfg(steps=5)
        keys = jax.random.split(jax.random.PRNGKey(0), f)
        dim = params.dim - 2
        jaxpr = jax.make_jaxpr(
            lambda th, ks: dfo.minimize_fleet(loss, th, ks, cfg).theta
        )(jnp.zeros((f, dim)), keys)
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1
        proj_shape = tuple(params.projections.shape)
        return [
            e for e in all_eqns(scans[0].params["jaxpr"].jaxpr)
            if e.primitive.name == "transpose"
            and tuple(e.invars[0].aval.shape) == proj_shape
        ]

    def test_no_projection_transpose_in_scanned_step(self):
        """The session-hoisted loss (make_loss_fn, kernel path) pre-converts
        the weight layout: zero transposes of the projection tensor inside
        the scan body."""
        sk, params, _ = _sketch_problem(d=7, rows=48)
        loss = regression.make_loss_fn(sk, params, engine="kernel")
        assert self._scan_body_transposes(loss, params) == []

    def test_detector_catches_unhoisted_loss(self):
        """Positive control: the per-call ops.query_theta convenience DOES
        transpose inside the step — proving the jaxpr assertion has teeth."""
        from repro.kernels import ops as kernel_ops

        sk, params, _ = _sketch_problem(d=7, rows=48)
        unhoisted = jax.jit(
            lambda th: kernel_ops.query_theta(sk, params, th, paired=True)
        )
        assert len(self._scan_body_transposes(unhoisted, params)) >= 1


class TestFleetFit:
    def _problem(self):
        kz, kp = jax.random.split(jax.random.PRNGKey(0))
        z = 0.5 * jax.random.normal(kz, (300, 5))
        zs, _ = lsh.scale_to_unit_ball(z)
        params = lsh.init_srp(kp, 64, 3, 5 + 2)
        sk = sketch_lib.sketch_dataset(params, zs, batch=50, paired=True)
        return sk, params

    def test_one_device_mesh_equals_unsharded(self):
        """fleet_fit over a 1-device mesh is the same compiled program as the
        local run: loss traces bit-for-bit, thetas to fp tolerance (the
        refine pass's eigensolve may lower differently under sharding)."""
        sk, params = self._problem()
        f = 4
        keys = jax.random.split(jax.random.PRNGKey(5), f)
        theta0 = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (f, 5))
        cfg = _fleet_cfg(steps=20)
        mesh = Mesh(np.array(jax.devices()[:1]), ("fleet",))
        local = distributed.fleet_fit(sk, params, theta0, keys, cfg, mesh=None)
        sharded = distributed.fleet_fit(sk, params, theta0, keys, cfg,
                                        mesh=mesh)
        np.testing.assert_array_equal(np.asarray(local.losses),
                                      np.asarray(sharded.losses))
        np.testing.assert_array_equal(np.asarray(local.theta),
                                      np.asarray(sharded.theta))

    def test_one_device_mesh_with_refine(self):
        sk, params = self._problem()
        f = 2
        keys = jax.random.split(jax.random.PRNGKey(2), f)
        theta0 = jnp.zeros((f, 5))
        cfg = _fleet_cfg(steps=10)
        mesh = Mesh(np.array(jax.devices()[:1]), ("fleet",))
        local = distributed.fleet_fit(sk, params, theta0, keys, cfg,
                                      mesh=None, refine_steps=1)
        sharded = distributed.fleet_fit(sk, params, theta0, keys, cfg,
                                        mesh=mesh, refine_steps=1)
        np.testing.assert_array_equal(np.asarray(local.losses),
                                      np.asarray(sharded.losses))
        np.testing.assert_allclose(np.asarray(local.theta),
                                   np.asarray(sharded.theta), atol=1e-4)

    def test_indivisible_fleet_raises(self):
        sk, params = self._problem()
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("fleet",))
        from repro.sharding import specs

        try:
            specs.check_fleet_divisible(3, Mesh(np.array(jax.devices()[:1]),
                                                ("fleet",)), "fleet")
        except ValueError:
            assert False, "F=3 divides a 1-device mesh"
        # a fake 2-wide axis cannot split F=3; simulate via the checker alone
        class FakeMesh:
            shape = {"fleet": 2}

        try:
            specs.check_fleet_divisible(3, FakeMesh(), "fleet")
            assert False, "expected ValueError"
        except ValueError:
            pass


class TestRegressionRestarts:
    def _problem(self):
        from repro.data import datasets

        return datasets.make_regression(jax.random.PRNGKey(0), 400, 4,
                                        noise=0.2, condition=3)

    def _cfg(self, **kw):
        base = dict(
            rows=512,
            dfo=dfo.DFOConfig(steps=80, num_queries=8, sigma=0.5,
                              sigma_decay=0.995, learning_rate=2.0,
                              decay=0.995, average_tail=0.5),
        )
        base.update(kw)
        return regression.StormRegressorConfig(**base)

    def test_restart_fleet_beats_trivial_and_reports_fleet_losses(self):
        x, y, _ = self._problem()
        fit = regression.fit(jax.random.PRNGKey(1), x, y,
                             self._cfg(restarts=4))
        assert fit.fleet_losses.shape == (4,)
        assert float(fit.mse(x, y)) < 0.5 * float(jnp.var(y))

    def test_selected_member_is_no_worse_than_baseline_member(self):
        """Selection by final sketch-loss: the chosen theta's sketch loss is
        <= every member's (member 0 is the old single-fit seed)."""
        x, y, _ = self._problem()
        fit = regression.fit(jax.random.PRNGKey(2), x, y,
                             self._cfg(restarts=6))
        loss = regression.make_loss_fn(fit.sketch, fit.params,
                                       engine="scan", d=4)
        chosen = jnp.concatenate([fit.theta_std, jnp.asarray([-1.0])])
        assert float(loss(chosen[None])[0]) <= float(
            jnp.min(fit.fleet_losses)) + 1e-6

    def test_basin_average_mode_runs(self):
        x, y, _ = self._problem()
        fit = regression.fit(
            jax.random.PRNGKey(3), x, y,
            self._cfg(restarts=4, restart_select="average"),
        )
        assert np.isfinite(float(fit.mse(x, y)))

    def test_unknown_restart_select_raises(self):
        x, y, _ = self._problem()
        try:
            regression.fit(jax.random.PRNGKey(0), x, y,
                           self._cfg(restart_select="avg"))
            assert False, "expected ValueError for restart_select typo"
        except ValueError:
            pass

    def test_restarts_one_is_default_path(self):
        """restarts=1 and the default config run the identical program."""
        x, y, _ = self._problem()
        a = regression.fit(jax.random.PRNGKey(4), x, y, self._cfg())
        b = regression.fit(jax.random.PRNGKey(4), x, y,
                           self._cfg(restarts=1))
        np.testing.assert_array_equal(np.asarray(a.theta), np.asarray(b.theta))
