"""One-pass hot-path tests: paired PRP insert, tiled query, stream engine.

These cover the fused antithetic insert (``paired_hash_histogram``), the
query kernel's m-tiling (no large-m fallback), and the streaming kernel
engine (``ops.sketch_stream`` / ``sketch_dataset(engine=...)``). Counts are
integers, so kernel-vs-reference checks are bit-exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr import all_eqns, pallas_kernels

from repro.core import lsh, sketch as sketch_lib
from repro.kernels import ops, ref
from repro.kernels import sketch_query as query_kernel
from repro.kernels import storm_sketch as histogram_kernel

jax.config.update("jax_platform_name", "cpu")


def _paired_inputs(n, d, r, p, seed=0):
    kz, kw, km = jax.random.split(jax.random.PRNGKey(seed), 3)
    z = jax.random.normal(kz, (n, d)) * (0.5 / jnp.sqrt(d))
    w = jax.random.normal(kw, (p, d + 2, r))
    mask = (jax.random.uniform(km, (n,)) > 0.25).astype(jnp.float32)
    return z, w, mask


PAIRED_SHAPES = [
    (8, 4, 8, 1),        # minimal
    (100, 9, 64, 4),     # paper-scale regression
    (300, 130, 256, 4),  # d > block boundary
    (513, 48, 300, 2),   # n, r off tile boundaries
    (64, 256, 128, 6),   # pair-histogram fallback path (B*B > 4096)
]


class TestPairedInsertRef:
    @pytest.mark.parametrize("n,d,r,p", PAIRED_SHAPES)
    def test_equals_two_single_sided(self, n, d, r, p):
        """The one-pass oracle == the two single-sided histograms it fuses.

        The negative-side projection is derived as ``2t - proj(aug(z))``
        rather than recomputed, so a projection landing within one rounding
        error of zero can flip its sign bit between the two formulations and
        move that point to a sibling bucket *in the same row*. Row masses are
        always exact; a tiny L1 tie budget absorbs the measure-zero flips.
        """
        z, w, mask = _paired_inputs(n, d, r, p)
        got = np.asarray(ref.paired_hash_histogram(z, w, mask))
        want = ref.hash_histogram(lsh.augment_data(z), w, mask)
        want = np.asarray(want + ref.hash_histogram(lsh.augment_data(-z), w, mask))
        np.testing.assert_array_equal(got.sum(axis=1), want.sum(axis=1))
        assert np.abs(got - want).sum() <= 4, np.abs(got - want).sum()

    def test_codes_match_srp_hash(self):
        """Positive/negative code sets == explicit hashes of aug(+/-z)."""
        z, w, _ = _paired_inputs(200, 11, 96, 4)
        cpos, cneg = ref.paired_srp_hash(z, w)
        np.testing.assert_array_equal(
            np.asarray(cpos), np.asarray(ref.srp_hash(lsh.augment_data(z), w))
        )
        np.testing.assert_array_equal(
            np.asarray(cneg), np.asarray(ref.srp_hash(lsh.augment_data(-z), w))
        )

    def test_mass_conservation(self):
        """A paired insert adds exactly 2 per row per unmasked point."""
        z, w, mask = _paired_inputs(211, 13, 48, 4)
        got = ref.paired_hash_histogram(z, w, mask)
        assert int(np.asarray(got).sum()) == 2 * int(mask.sum()) * 48


class TestPairedInsertKernel:
    @pytest.mark.parametrize("n,d,r,p", PAIRED_SHAPES)
    def test_matches_oracle(self, n, d, r, p):
        z, w, mask = _paired_inputs(n, d, r, p)
        got = histogram_kernel.paired_hash_histogram(z, w, mask, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.paired_hash_histogram(z, w, mask))
        )

    @pytest.mark.parametrize("block_n", [8, 32, 128])
    def test_block_invariance(self, block_n):
        """Counts must not depend on the tiling."""
        z, w, mask = _paired_inputs(57, 24, 40, 3, seed=block_n)
        got = histogram_kernel.paired_hash_histogram(
            z, w, mask, interpret=True, block_n=block_n, block_r=32, block_d=16
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.paired_hash_histogram(z, w, mask))
        )


def _count_projection_dots(fn, *args, contract_size):
    """Number of dot_generals contracting over a dimension of ``contract_size``.

    Walks nested jaxprs (pjit/scan bodies included), so jitted entry points
    count too. Used to assert the paired insert runs its projection matmuls
    exactly once per batch.
    """
    count = 0
    for eqn in all_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "dot_general":
            (lhs_contract, _), _ = eqn.params["dimension_numbers"]
            shape = eqn.invars[0].aval.shape
            if any(shape[i] == contract_size for i in lhs_contract):
                count += 1
    return count


class TestProjectionWorkHalved:
    def test_paired_build_runs_projections_once(self):
        """build_sketch(paired=True) runs the projection matmul once per
        batch; the two-single-sided formulation it replaced ran 2p."""
        d, r, p = 7, 64, 3
        d_aug = d + 2  # unique among all dims in play (n=50, r=64, B=8)
        params = lsh.init_srp(jax.random.PRNGKey(0), r, p, d_aug)
        z, w, mask = _paired_inputs(50, d, r, p)

        paired = _count_projection_dots(
            lambda zz: ops.build_sketch(params, zz, paired=True, mode="ref"),
            z, contract_size=d_aug,
        )
        two_sided = _count_projection_dots(
            lambda zz: ref.hash_histogram(lsh.augment_data(zz), w, mask)
            + ref.hash_histogram(lsh.augment_data(-zz), w, mask),
            z, contract_size=d_aug,
        )
        assert two_sided == 2 * p
        assert paired == p  # one pass: p plane matmuls over the batch, not 2p


class TestTiledQuery:
    @pytest.mark.parametrize("m", [129, 512, 1024, 4096])
    def test_large_m_matches_oracle_bit_identical(self, m):
        """No reference fallback: the kernel tiles over query blocks and the
        row-sums of integer counts are exact in f32, so means are bit-equal."""
        d, r, p = 16, 192, 4
        kq, kw, kc = jax.random.split(jax.random.PRNGKey(m), 3)
        q = jax.random.normal(kq, (m, d))
        w = jax.random.normal(kw, (p, d, r))
        counts = jax.random.randint(kc, (r, 1 << p), 0, 1000)
        got = query_kernel.sketch_query(q, w, counts, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.sketch_query(q, w, counts))
        )

    def test_ops_dispatch_runs_kernel_for_large_m(self):
        """ops.sketch_query keeps m=4096 on the kernel path (mode=interpret
        forces the kernel; before the m-tiling this path asserted m<=128)."""
        m, d, r, p = 4096, 24, 64, 3
        kq, kw, kc = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(kq, (m, d))
        w = jax.random.normal(kw, (p, d, r))
        counts = jax.random.randint(kc, (r, 1 << p), 0, 800)
        got = ops.sketch_query(q, w, counts, mode="interpret")
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref.sketch_query(q, w, counts))
        )

    def test_query_theta_large_batch(self):
        params = lsh.init_srp(jax.random.PRNGKey(2), 96, 4, 9)
        z, _, _ = _paired_inputs(150, 7, 96, 4, seed=3)
        sk = ops.build_sketch(params, z, paired=True, mode="interpret")
        tt = jax.random.normal(jax.random.PRNGKey(4), (300, 7))
        est_k = ops.query_theta(sk, params, tt, paired=True, mode="interpret")
        est_c = sketch_lib.query_theta(sk, params, tt, paired=True)
        np.testing.assert_allclose(np.asarray(est_k), np.asarray(est_c),
                                   rtol=1e-5)


# (S, Q, R, counter dtype, block_m, m): S in {1, 8, 64}, Q in {8, 16, 64,
# 128}, R below and above one 512-row tile, m off a multiple of the query
# tile (block_m=48 at Q=16 tiles 3 tables; m=100 ends inside a table) and Q
# above block_m (one table split over tiles).
TENANT_MAJOR_CASES = [
    (1, 8, 40, jnp.int32, 128, None),
    (8, 16, 40, jnp.int16, 128, None),
    (8, 64, 600, jnp.int8, 128, None),
    (64, 16, 600, jnp.int32, 128, None),
    (64, 8, 40, jnp.int8, 128, None),
    (1, 128, 600, jnp.int16, 128, None),
    (8, 128, 40, jnp.int32, 64, None),
    (8, 16, 40, jnp.int32, 48, None),
    (8, 16, 600, jnp.int16, 128, 100),
]


class TestTenantMajorQuery:
    """``points_per_table=Q`` (point i reads table i // Q) fetches only each
    query tile's tables; integer counts keep it bit-equal to the one-hot
    kernel and the oracle for every counter width."""

    @staticmethod
    def _case(s, q, r, dtype, m, p=3, d=7):
        m = s * q if m is None else m
        kq, kw, kc = jax.random.split(jax.random.PRNGKey(s * q + r), 3)
        # Row sums stay below 2^24, where f32 adds integers exactly.
        hi = {jnp.int8: 127, jnp.int16: 16384}.get(dtype, 1 << 14)
        return (jax.random.normal(kq, (m, d)),
                jax.random.normal(kw, (p, d, r)),
                jax.random.randint(kc, (s, r, 1 << p), 0, hi).astype(dtype),
                jnp.arange(m, dtype=jnp.int32) // q)

    @pytest.mark.parametrize("s,q,r,dtype,block_m,m", TENANT_MAJOR_CASES)
    def test_bit_equal_to_one_hot_and_oracle(self, s, q, r, dtype, block_m, m):
        qv, w, counts, idx = self._case(s, q, r, dtype, m)
        got = query_kernel.sketch_query_banked(
            qv, w, counts, points_per_table=q, block_m=block_m,
            interpret=True)
        one_hot = query_kernel.sketch_query_banked(qv, w, counts, idx,
                                                   interpret=True)
        want = ref.sketch_query_banked(qv, w, counts, idx)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(one_hot))

    @pytest.mark.parametrize("q,path,kernel", [
        (16, "tenant_major", "_query_kernel_tenant_major"),
        (12, "one_hot", "_query_kernel"),
    ])
    def test_ops_engages_only_at_multiples_of_eight(self, q, path, kernel):
        qv, w, counts, idx = self._case(4, q, 40, jnp.int32, None)
        assert ops.query_path("interpret", qv.shape[1], q) == path
        assert ops.query_path("ref", qv.shape[1], q) == "ref"
        call = lambda *a: ops.sketch_query(*a, mode="interpret",  # noqa: E731
                                           points_per_table=q)
        assert pallas_kernels(jax.make_jaxpr(call)(qv, w, counts)) == [kernel]
        np.testing.assert_array_equal(
            np.asarray(call(qv, w, counts)),
            np.asarray(ref.sketch_query_banked(qv, w, counts, idx)))

    def test_rejects_bad_layouts(self):
        qv, w, counts, idx = self._case(4, 12, 40, jnp.int32, None)
        with pytest.raises(ValueError, match="multiple of 8"):
            query_kernel.sketch_query_banked(qv, w, counts,
                                             points_per_table=12)
        with pytest.raises(ValueError, match="exactly one"):
            query_kernel.sketch_query_banked(qv, w, counts, idx,
                                             points_per_table=16)
        with pytest.raises(ValueError, match="tables"):  # 48 points > 4·8
            ops.sketch_query(qv, w, counts, points_per_table=8)
        with pytest.raises(ValueError, match="not both"):
            ops.sketch_query(qv, w, counts, sketch_idx=idx,
                             points_per_table=12)


class TestBuildSketchPaired:
    def test_equals_sum_of_single_sided_builds(self):
        """build_sketch(paired=True) == two single-sided builds summed."""
        params = lsh.init_srp(jax.random.PRNGKey(5), 64, 4, 8)
        z, _, mask = _paired_inputs(123, 6, 64, 4, seed=6)
        paired = ops.build_sketch(params, z, mask=mask, paired=True, mode="ref")
        pos = ops.build_sketch(params, lsh.augment_data(z), mask=mask,
                               paired=False, mode="ref")
        neg = ops.build_sketch(params, lsh.augment_data(-z), mask=mask,
                               paired=False, mode="ref")
        np.testing.assert_array_equal(
            np.asarray(paired.counts), np.asarray(pos.counts + neg.counts)
        )
        assert int(paired.n) == int(pos.n)

    def test_interpret_matches_ref_mode(self):
        params = lsh.init_srp(jax.random.PRNGKey(7), 80, 3, 10)
        z, _, mask = _paired_inputs(97, 8, 80, 3, seed=8)
        a = ops.build_sketch(params, z, mask=mask, paired=True, mode="ref")
        b = ops.build_sketch(params, z, mask=mask, paired=True, mode="interpret")
        np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))


class TestSketchStream:
    def test_matches_scan_engine_paired(self):
        params = lsh.init_srp(jax.random.PRNGKey(9), 72, 4, 9)
        z, _, _ = _paired_inputs(257, 7, 72, 4, seed=10)
        fused = ops.sketch_stream(params, z, batch=64, paired=True, mode="ref")
        scan = sketch_lib.sketch_dataset(params, z, batch=64, paired=True,
                                         engine="scan")
        np.testing.assert_array_equal(np.asarray(fused.counts),
                                      np.asarray(scan.counts))
        assert int(fused.n) == int(scan.n)

    def test_matches_scan_engine_unpaired(self):
        params = lsh.init_srp(jax.random.PRNGKey(11), 48, 3, 5)
        z = 0.4 * jax.random.normal(jax.random.PRNGKey(12), (130, 5))
        fused = ops.sketch_stream(params, z, batch=32, paired=False, mode="ref")
        scan = sketch_lib.sketch_dataset(params, z, batch=32, paired=False,
                                         engine="scan")
        np.testing.assert_array_equal(np.asarray(fused.counts),
                                      np.asarray(scan.counts))

    def test_masked_stream(self):
        params = lsh.init_srp(jax.random.PRNGKey(13), 32, 2, 6)
        z, _, _ = _paired_inputs(90, 4, 32, 2, seed=14)
        mask = jnp.concatenate([jnp.ones(60), jnp.zeros(30)])
        full = ops.sketch_stream(params, z, mask=mask, batch=16, paired=True,
                                 mode="ref")
        trunc = ops.sketch_stream(params, z[:60], batch=16, paired=True,
                                  mode="ref")
        np.testing.assert_array_equal(np.asarray(full.counts),
                                      np.asarray(trunc.counts))
        assert int(full.n) == 60

    def test_sketch_dataset_kernel_engine_dispatch(self):
        """engine='kernel' routes through ops.sketch_stream, counts equal."""
        params = lsh.init_srp(jax.random.PRNGKey(15), 40, 3, 7)
        z, _, _ = _paired_inputs(101, 5, 40, 3, seed=16)
        kern = sketch_lib.sketch_dataset(params, z, batch=25, paired=True,
                                         engine="kernel")
        scan = sketch_lib.sketch_dataset(params, z, batch=25, paired=True,
                                         engine="scan")
        np.testing.assert_array_equal(np.asarray(kern.counts),
                                      np.asarray(scan.counts))
        assert int(kern.n) == int(scan.n)
