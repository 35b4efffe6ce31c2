"""Fleet-native classification & probe drivers (DESIGN.md §8.4).

Pins the PR-3 contracts:

* **Equivalence** — ``classification.fit(restarts=1)`` and
  ``fit_probe(restarts=1)`` are bit-identical to the single-iterate fits
  (the pre-fleet reference implementations, inlined here); the fleet paths
  equal a loop of single ``dfo.minimize`` calls per member.
* **Query batching** — one fused loss call of ``F*(2k+1)`` points per DFO
  step for both new drivers (jaxpr gather count against the counter table).
* **Hoisted weights** — the classification margin loss on the kernel engine
  carries no per-step weight-layout transpose.
* **Sharded probes** — ``fit_probe_sharded`` (mesh and mesh-free) agrees
  with the local fleet fit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from _jaxpr import all_eqns

from repro.core import (classification, dfo, fleet, lsh, probes,
                        sketch as sketch_lib)
from repro.data import datasets

jax.config.update("jax_platform_name", "cpu")


def _scan_gathers(loss, dim, counter_shape, f=4, steps=6):
    cfg = dfo.DFOConfig(steps=steps, num_queries=4, sigma=0.4,
                        learning_rate=0.5, decay=0.99, average_tail=0.4)
    keys = jax.random.split(jax.random.PRNGKey(0), f)
    jaxpr = jax.make_jaxpr(
        lambda th, ks: dfo.minimize_fleet(loss, th, ks, cfg).theta
    )(jnp.zeros((f, dim)), keys)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    return [
        e for e in all_eqns(scans[0].params["jaxpr"].jaxpr)
        if e.primitive.name == "gather"
        and tuple(e.invars[0].aval.shape) == tuple(counter_shape)
    ]


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------


def _cls_config(restarts=1, **kw):
    base = dict(
        rows=128, planes=1, restarts=restarts,
        dfo=dfo.DFOConfig(steps=40, num_queries=6, sigma=0.5,
                          learning_rate=1.0, decay=0.99, average_tail=0.5),
    )
    base.update(kw)
    return classification.StormClassifierConfig(**base)


@pytest.fixture(scope="module")
def cls_blobs():
    return datasets.make_classification(jax.random.PRNGKey(0), 400, 3,
                                        margin=0.7)


def _single_fit_reference(key, x, y, config):
    """The single-iterate classification fit, inlined: the pre-fleet program
    with the (fixed) split-key discipline."""
    k_hash, k_rest = jax.random.split(key)
    k_init, k_dfo = jax.random.split(k_rest)
    d = x.shape[-1]
    z = -y[:, None] * x
    z_scaled, _ = lsh.scale_to_unit_ball(z, config.norm_slack)
    z_aug = lsh.augment_data(z_scaled)
    params = lsh.init_srp(k_hash, config.rows, config.planes, d + 2)
    sk = sketch_lib.sketch_dataset(params, z_aug, batch=config.batch,
                                   paired=False)
    scale = 2.0 ** config.planes

    def loss_fn(thetas):
        q_aug = lsh.augment_query(lsh.normalize_query(thetas))
        codes = lsh.srp_codes(params, q_aug)
        return scale * sketch_lib.query(sk, codes, paired=False)

    theta0 = config.init_scale * jax.random.normal(k_init, (d,))
    result = dfo.minimize(jax.jit(loss_fn), theta0, k_dfo, config.dfo)
    return result


class TestClassificationFleet:
    def test_restarts_one_is_single_fit_bit_for_bit(self, cls_blobs):
        """fit(restarts=1) reproduces the single-iterate fit exactly —
        same sketch, same init, same DFO trajectory, same theta."""
        x, y, _ = cls_blobs
        cfg = _cls_config()
        fit = classification.fit(jax.random.PRNGKey(1), x, y, cfg)
        ref = _single_fit_reference(jax.random.PRNGKey(1), x, y, cfg)
        np.testing.assert_array_equal(np.asarray(fit.theta),
                                      np.asarray(ref.theta))
        np.testing.assert_array_equal(np.asarray(fit.losses),
                                      np.asarray(ref.losses))

    def test_fleet_matches_loop_of_singles(self, cls_blobs):
        """fit(restarts=F) ≡ F independent minimize calls on the seeded
        inits/ladders: loss traces bit-for-bit at every step, final thetas
        to 1-ULP (the Polyak tail-mean reduction may vectorize differently
        for a (T, F, d) block than a (T, 1, d) one on CPU XLA)."""
        x, y, _ = cls_blobs
        f = 3
        cfg = _cls_config(restarts=f)
        fit = classification.fit(jax.random.PRNGKey(2), x, y, cfg)

        # Rebuild the seeding exactly as fit() does.
        k_hash, k_rest = jax.random.split(jax.random.PRNGKey(2))
        k_init, k_dfo = jax.random.split(k_rest)
        d = x.shape[-1]
        theta0 = cfg.init_scale * jax.random.normal(k_init, (d,))
        keys, inits, sigmas, lrs = fleet.seed_fleet(
            k_dfo, f, d, cfg.dfo, fleet.FleetConfig(), theta0=theta0
        )
        loss = classification.make_margin_loss_fn(fit.sketch, fit.params,
                                                  cfg.planes, engine="scan")
        fleet_res = dfo.minimize_fleet(loss, inits, keys, cfg.dfo,
                                       sigma=sigmas, learning_rate=lrs)
        loop = [
            dfo.minimize(
                loss, inits[i], keys[i],
                dataclasses.replace(cfg.dfo, sigma=float(sigmas[i]),
                                    learning_rate=float(lrs[i])),
            )
            for i in range(f)
        ]
        np.testing.assert_array_equal(
            np.asarray(fleet_res.losses),
            np.asarray(jnp.stack([r.losses for r in loop])),
        )
        loop_thetas = jnp.stack([r.theta for r in loop])
        np.testing.assert_allclose(np.asarray(fleet_res.theta),
                                   np.asarray(loop_thetas), atol=1e-6)
        # The public fit() ran the identical fleet program.
        np.testing.assert_array_equal(np.asarray(fit.fleet_losses),
                                      np.asarray(loss(fleet_res.theta)))
        np.testing.assert_array_equal(
            np.asarray(fit.theta),
            np.asarray(fleet_res.theta[int(jnp.argmin(fit.fleet_losses))]),
        )

    def test_fleet_restarts_accuracy_and_shapes(self, cls_blobs):
        x, y, _ = cls_blobs
        fit = classification.fit(jax.random.PRNGKey(3), x, y,
                                 _cls_config(restarts=4))
        assert fit.fleet_losses.shape == (4,)
        assert float(fit.accuracy(x, y)) > 0.85

    def test_selected_member_minimizes_sketch_loss(self, cls_blobs):
        """Selection contract: the returned theta's margin loss is <= every
        member's final loss."""
        x, y, _ = cls_blobs
        cfg = _cls_config(restarts=5)
        fit = classification.fit(jax.random.PRNGKey(4), x, y, cfg)
        loss = classification.make_margin_loss_fn(fit.sketch, fit.params,
                                                  cfg.planes, engine="scan")
        chosen = float(loss(fit.theta[None])[0])
        assert chosen <= float(jnp.min(fit.fleet_losses)) + 1e-6

    def test_basin_average_mode_runs(self, cls_blobs):
        x, y, _ = cls_blobs
        fit = classification.fit(
            jax.random.PRNGKey(5), x, y,
            _cls_config(restarts=4, restart_select="average"),
        )
        assert np.isfinite(float(fit.accuracy(x, y)))

    def test_unknown_restart_select_raises(self, cls_blobs):
        x, y, _ = cls_blobs
        with pytest.raises(ValueError):
            classification.fit(jax.random.PRNGKey(0), x, y,
                               _cls_config(restart_select="avg"))

    def test_one_gather_per_step_in_jaxpr(self, cls_blobs):
        """Acceptance contract: the classification fleet step issues exactly
        ONE fused gather against the (R, B) counter table — one F*(2k+1)
        query serves the whole fleet."""
        x, y, _ = cls_blobs
        cfg = _cls_config()
        fit = classification.fit(jax.random.PRNGKey(6), x, y, cfg)
        loss = classification.make_margin_loss_fn(fit.sketch, fit.params,
                                                  cfg.planes, engine="scan")
        gathers = _scan_gathers(loss, x.shape[-1], fit.sketch.counts.shape)
        assert len(gathers) == 1, f"expected 1 counter gather, got {len(gathers)}"

    def test_no_weight_transpose_in_scanned_step_kernel_engine(self, cls_blobs):
        """The margin loss rides the hoisted-weight query: no
        (R, p, d) -> (p, d, R) transpose of the projection tensor inside the
        scanned DFO step on the kernel engine."""
        x, y, _ = cls_blobs
        cfg = _cls_config()
        fit = classification.fit(jax.random.PRNGKey(7), x, y, cfg)
        loss = classification.make_margin_loss_fn(fit.sketch, fit.params,
                                                  cfg.planes, engine="kernel")
        d = x.shape[-1]
        cfg_d = dfo.DFOConfig(steps=5, num_queries=4, sigma=0.4,
                              learning_rate=0.5, decay=0.99)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        jaxpr = jax.make_jaxpr(
            lambda th, ks: dfo.minimize_fleet(loss, th, ks, cfg_d).theta
        )(jnp.zeros((3, d)), keys)
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1
        proj_shape = tuple(fit.params.projections.shape)
        transposes = [
            e for e in all_eqns(scans[0].params["jaxpr"].jaxpr)
            if e.primitive.name == "transpose"
            and tuple(e.invars[0].aval.shape) == proj_shape
        ]
        assert transposes == []


# ---------------------------------------------------------------------------
# Probes
# ---------------------------------------------------------------------------


def _probe_problem(d_model=6, n=300, seed=0):
    kf, kw, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    feats = jax.random.normal(kf, (n, d_model))
    w_true = jax.random.normal(kw, (d_model,))
    targets = feats @ w_true + 0.05 * jax.random.normal(kn, (n,))
    state = probes.sketch_features(jax.random.PRNGKey(seed + 1), feats,
                                   targets, probes.ProbeConfig(rows=256))
    return feats, targets, state


def _probe_dfo(steps=40):
    return dfo.DFOConfig(steps=steps, num_queries=6, sigma=0.5,
                         sigma_decay=0.995, learning_rate=2.0, decay=0.995,
                         average_tail=0.5)


def _old_fit_probe_reference(key, state, d_model, dfo_config, l2=3e-2):
    """The pre-PR-3 fit_probe, inlined (single iterate, zero-guard
    selection, un-standardize); its ridge sums squares in the library's
    fixed order (``lsh.row_sq_norm``)."""

    def loss_fn(thetas):
        est = sketch_lib.query_theta(state.sketch, state.params, thetas,
                                     paired=True)
        if l2 > 0.0:
            est = est + l2 * lsh.row_sq_norm(thetas[..., :d_model])
        return est

    proj = dfo.pin_last_coordinate(-1.0)
    jloss = jax.jit(loss_fn)
    result = dfo.minimize(jloss, jnp.zeros((d_model + 1,)), key, dfo_config,
                          project=proj)
    both = jnp.stack([result.theta, proj(jnp.zeros((d_model + 1,)))])
    theta_tilde = both[jnp.argmin(jloss(both))]
    theta_std = theta_tilde[:d_model]
    theta = state.y_scale * theta_std / state.x_scale
    intercept = state.y_mean - jnp.dot(state.x_mean, theta)
    return theta, intercept


class TestProbeFleet:
    def test_restarts_one_bit_identical_to_pre_pr_single(self):
        """fit_probe(restarts=1) is the pre-PR-3 single fit, bit-for-bit."""
        _, _, state = _probe_problem()
        cfg_d = _probe_dfo()
        fit = probes.fit_probe(jax.random.PRNGKey(9), state, 6,
                               dfo_config=cfg_d)
        theta_ref, intercept_ref = _old_fit_probe_reference(
            jax.random.PRNGKey(9), state, 6, cfg_d
        )
        np.testing.assert_array_equal(np.asarray(fit.theta),
                                      np.asarray(theta_ref))
        np.testing.assert_array_equal(np.asarray(fit.intercept),
                                      np.asarray(intercept_ref))

    def test_fleet_matches_loop_of_singles(self):
        """fit_probe(restarts=F) ≡ F independent minimize calls on the
        seeded inits/ladders (fleet_losses pins every member)."""
        _, _, state = _probe_problem(seed=2)
        d_model, f = 6, 3
        cfg_d = _probe_dfo()
        fit = probes.fit_probe(jax.random.PRNGKey(11), state, d_model,
                               dfo_config=cfg_d, restarts=f)
        loss = fleet.make_loss_fn(state.sketch, state.params, paired=True,
                                  l2=3e-2, engine="scan", d=d_model)
        proj = dfo.pin_last_coordinate(-1.0)
        keys, inits, sigmas, lrs = fleet.seed_fleet(
            jax.random.PRNGKey(11), f, d_model + 1, cfg_d,
            fleet.FleetConfig()
        )
        loop = jnp.stack([
            dfo.minimize(
                loss, inits[i], keys[i],
                dataclasses.replace(cfg_d, sigma=float(sigmas[i]),
                                    learning_rate=float(lrs[i])),
                project=proj,
            ).theta
            for i in range(f)
        ])
        np.testing.assert_array_equal(np.asarray(fit.fleet_losses),
                                      np.asarray(loss(loop)))

    def test_refine_polish_uses_shared_key_convention(self):
        """fit_probe(refine_steps=1) equals minimize_fleet +
        quadratic_refine_fleet under fold_in(member_key, 1) — the one shared
        refine-key convention."""
        _, _, state = _probe_problem(seed=3)
        d_model, f = 6, 2
        cfg_d = _probe_dfo(steps=20)
        fit = probes.fit_probe(jax.random.PRNGKey(13), state, d_model,
                               dfo_config=cfg_d, restarts=f, refine_steps=1,
                               refine_radius=0.2)
        loss = fleet.make_loss_fn(state.sketch, state.params, paired=True,
                                  l2=3e-2, engine="scan", d=d_model)
        proj = dfo.pin_last_coordinate(-1.0)
        keys, inits, sigmas, lrs = fleet.seed_fleet(
            jax.random.PRNGKey(13), f, d_model + 1, cfg_d,
            fleet.FleetConfig()
        )
        res = dfo.minimize_fleet(loss, inits, keys, cfg_d, project=proj,
                                 sigma=sigmas, learning_rate=lrs)
        refine_keys = jax.vmap(lambda k: jax.random.fold_in(k, 1))(keys)
        thetas = dfo.quadratic_refine_fleet(loss, res.theta, refine_keys,
                                            radius=0.2, project=proj)
        np.testing.assert_array_equal(np.asarray(fit.fleet_losses),
                                      np.asarray(loss(thetas)))

    def test_fleet_recovers_head(self):
        feats, targets, state = _probe_problem(seed=4)
        fit = probes.fit_probe(jax.random.PRNGKey(15), state, 6,
                               dfo_config=_probe_dfo(steps=120), restarts=4)
        assert fit.fleet_losses.shape == (4,)
        assert float(fit.mse(feats, targets)) < float(jnp.var(targets))

    def test_one_gather_per_step_in_jaxpr(self):
        """The probe fleet step (d_model + 1 dims) issues exactly ONE fused
        counter gather."""
        _, _, state = _probe_problem(seed=5)
        loss = fleet.make_loss_fn(state.sketch, state.params, paired=True,
                                  l2=3e-2, engine="scan", d=6)
        gathers = _scan_gathers(loss, 7, state.sketch.counts.shape)
        assert len(gathers) == 1


class TestFitProbeSharded:
    def test_meshless_matches_local_fleet(self):
        """fit_probe_sharded(mesh=None) runs the same seeded fleet as
        fit_probe, compiled as one program. Bit-equality is not guaranteed
        across the two compilations (the bucket-code sign test turns ULP
        noise into different hash gathers), so the contract is: identical
        seeding (the loss at the shared initial iterates matches) and
        equivalent training outcomes."""
        feats, targets, state = _probe_problem(seed=6)
        cfg_d = _probe_dfo(steps=25)
        local = probes.fit_probe(jax.random.PRNGKey(17), state, 6,
                                 dfo_config=cfg_d, restarts=4)
        sharded = probes.fit_probe_sharded(jax.random.PRNGKey(17), state, 6,
                                           mesh=None, restarts=4,
                                           dfo_config=cfg_d)
        # Same seeds: every member enters step 0 at the same iterate.
        np.testing.assert_allclose(np.asarray(local.losses[0]),
                                   np.asarray(sharded.losses[0]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(local.fleet_losses),
                                   np.asarray(sharded.fleet_losses),
                                   atol=5e-3)
        var = float(jnp.var(targets))
        assert float(local.mse(feats, targets)) < var
        assert float(sharded.mse(feats, targets)) < var

    def test_one_device_mesh_matches_meshless(self):
        _, _, state = _probe_problem(seed=7)
        cfg_d = _probe_dfo(steps=15)
        mesh = Mesh(np.array(jax.devices()[:1]), ("fleet",))
        a = probes.fit_probe_sharded(jax.random.PRNGKey(19), state, 6,
                                     mesh=None, restarts=2, dfo_config=cfg_d)
        b = probes.fit_probe_sharded(jax.random.PRNGKey(19), state, 6,
                                     mesh=mesh, restarts=2, dfo_config=cfg_d)
        np.testing.assert_array_equal(np.asarray(a.losses),
                                      np.asarray(b.losses))
        np.testing.assert_allclose(np.asarray(a.theta), np.asarray(b.theta),
                                   atol=1e-5)
