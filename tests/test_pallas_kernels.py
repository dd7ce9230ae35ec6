"""ISSUE 9 kernel-plane tests (interpret mode, CPU pseudo-cluster):
PCA fused moments + ALS batched normal-equation solve vs their XLA
references at every precision tier, plus the single-shot padding
regression for the K-Means kernel.

Compiled-mode legs live in ``tests_tpu/test_kernels_tpu.py`` (run by
dev/ci.sh when a TPU backend is present), so a Mosaic lowering
regression cannot ship green on this suite alone.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.ops import als_ops, stream_ops
from oap_mllib_tpu.ops.pallas.als_kernel import (
    factor_gram_pallas,
    pallas_solve_preferred,
    solve_normal_eq_pallas,
)
from oap_mllib_tpu.ops.pallas.pca_kernel import (
    covariance_pallas,
    pallas_gram_preferred,
    pca_moments_pallas,
)
from oap_mllib_tpu.ops.pca_ops import _covariance_jit, use_pallas_gram
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.utils import progcache


# ---------------------------------------------------------------------------
# PCA fused moments
# ---------------------------------------------------------------------------


class TestPcaMomentsKernel:
    def _data(self, rng, n=900, d=33, mean=5.0):
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) + mean)
        m = jnp.asarray((rng.random(n) < 0.95).astype(np.float32))
        return x, m

    def test_colsum_and_count_match_xla_bitwise(self, rng):
        """The mean-pass outputs are tier-independent exact f32 VPU
        reductions — single-tile inputs match the XLA colsum bitwise."""
        x, m = self._data(rng, n=512)
        _, cs, cnt = pca_moments_pallas(x, m, need_gram=False, interpret=True)
        ref = jnp.sum(x * m[:, None], axis=0)
        assert np.array_equal(np.asarray(cs), np.asarray(ref))
        assert float(cnt) == float(jnp.sum(m))

    def test_covariance_matches_xla_at_highest(self, rng):
        x, m = self._data(rng)
        nv = jnp.asarray(float(np.asarray(m).sum()))
        cov_p, mean_p = covariance_pallas(x, m, nv, interpret=True)
        cov_r, mean_r = _covariance_jit(x, m, nv)
        np.testing.assert_allclose(
            np.asarray(mean_p), np.asarray(mean_r), atol=2e-6
        )
        np.testing.assert_allclose(
            np.asarray(cov_p), np.asarray(cov_r), atol=2e-6
        )

    def test_bit_compatible_at_highest_on_exact_data(self, rng):
        """The "bit-compatible at highest" contract, on data where f32
        arithmetic is exact: small symmetric integer rows (mean exactly
        0, products and their sums exactly representable), so EVERY
        summation order yields identical bits — the kernel's tile
        accumulation must reproduce the XLA pass bit-for-bit.  On
        general data the two differ only by shape-dependent dot blocking
        (<= a few ulps, pinned by test_covariance_matches_xla)."""
        n, d = 1024, 17
        half = rng.integers(-3, 4, size=(n // 2, d)).astype(np.float32)
        x = jnp.asarray(np.concatenate([half, -half]))  # colsum == 0
        m = jnp.ones((n,), jnp.float32)
        nv = jnp.asarray(float(n))
        cov_p, mean_p = covariance_pallas(x, m, nv, interpret=True)
        cov_r, mean_r = _covariance_jit(x, m, nv)
        assert np.array_equal(np.asarray(mean_p), np.asarray(mean_r))
        assert np.array_equal(np.asarray(cov_p), np.asarray(cov_r))

    @pytest.mark.parametrize(
        "mode,alias,atol",
        [("high", "tf32", 5e-5), ("default", "bf16", 5e-3)],
    )
    def test_split_tiers_within_envelope(self, rng, mode, alias, atol):
        """The hand-rolled hi/lo tiers hold their envelopes, and the
        compute-policy aliases resolve to the same tier (what prices the
        bf16 policy ON Pallas)."""
        x, m = self._data(rng, mean=0.0)
        nv = jnp.asarray(float(np.asarray(m).sum()))
        cov_r, _ = _covariance_jit(x, m, nv)
        cov_t, _ = covariance_pallas(x, m, nv, mode=mode, interpret=True)
        np.testing.assert_allclose(
            np.asarray(cov_t), np.asarray(cov_r), atol=atol
        )
        cov_a, _ = covariance_pallas(x, m, nv, mode=alias, interpret=True)
        assert np.array_equal(np.asarray(cov_a), np.asarray(cov_t))

    def test_streamed_chunk_fns_match_xla(self, rng):
        """The streamed per-chunk accumulators (plain + Kahan) built on
        the kernel reproduce the XLA chunk fns exactly at highest."""
        x, m = self._data(rng, n=512)
        d = x.shape[1]
        mean = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
        cs_p = stream_ops._colsum_chunk_pallas(
            jnp.zeros((d,), jnp.float32), x, m, interpret=True
        )
        cs_r = stream_ops._colsum_chunk(jnp.zeros((d,), jnp.float32), x, m)
        assert np.array_equal(np.asarray(cs_p), np.asarray(cs_r))
        g_p = stream_ops._gram_chunk_pallas(
            jnp.zeros((d, d), jnp.float32), x, m, mean, "highest",
            interpret=True,
        )
        g_r = stream_ops._gram_chunk(
            jnp.zeros((d, d), jnp.float32), x, m, mean, "highest"
        )
        # shape-dependent dot blocking (the kernel contracts the padded
        # 128-column tile) allows ulp-level drift; exact-data bit parity
        # is pinned in test_bit_compatible_at_highest_on_exact_data
        np.testing.assert_allclose(
            np.asarray(g_p), np.asarray(g_r),
            atol=1e-5 * max(1.0, float(np.abs(np.asarray(g_r)).max())),
        )
        # Kahan-compensated pair (the bf16 policy's cross-chunk contract)
        t, c = stream_ops._colsum_chunk_pallas_comp(
            jnp.zeros((d,), jnp.float32), jnp.zeros((d,), jnp.float32),
            x, m, interpret=True,
        )
        t_r, c_r = stream_ops._colsum_chunk_comp(
            jnp.zeros((d,), jnp.float32), jnp.zeros((d,), jnp.float32), x, m
        )
        assert np.array_equal(np.asarray(t), np.asarray(t_r))
        g2, gc2 = stream_ops._gram_chunk_pallas_comp(
            jnp.zeros((d, d), jnp.float32), jnp.zeros((d, d), jnp.float32),
            x, m, mean, "default", interpret=True,
        )
        assert np.isfinite(np.asarray(g2)).all()

    def test_bad_mode_and_bad_kernel_cfg_raise(self, rng):
        x, m = self._data(rng, n=64)
        with pytest.raises(ValueError, match="mode"):
            pca_moments_pallas(x, m, mode="fast", interpret=True)
        with pytest.raises(ValueError, match="pca_kernel"):
            use_pallas_gram("fastest", 8, "highest", np.float32)

    def test_dispatch_rule(self):
        # CPU backend: never dispatches, but the preference rule and the
        # validation run on every fit
        assert not use_pallas_gram("auto", 64, "highest", np.float32)
        assert pallas_gram_preferred(64, "default")  # bf16 ON pallas
        assert not pallas_gram_preferred(4096, "highest")  # VMEM bound

    def test_streamed_covariance_validates_kernel_cfg(self, rng):
        from oap_mllib_tpu.data.stream import ChunkSource

        set_config(pca_kernel="nope")
        data = rng.normal(size=(64, 5)).astype(np.float32)
        src = ChunkSource(
            lambda: iter([data]), n_features=5, chunk_rows=32, n_rows=64
        )
        with pytest.raises(ValueError, match="pca_kernel"):
            stream_ops.covariance_streamed(src, np.float32)


# ---------------------------------------------------------------------------
# ALS batched normal-equation solve
# ---------------------------------------------------------------------------


def _spd_batch(rng, n, r, reg_floor=0.5):
    m = rng.normal(size=(n, r, r)).astype(np.float32)
    a = jnp.asarray(np.einsum("nij,nkj->nik", m, m) + reg_floor * np.eye(r))
    b = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
    n_reg = jnp.asarray(
        (rng.random(n) > 0.1).astype(np.float32) * rng.integers(1, 50, n)
    )
    return a, b, n_reg


class TestAlsSolveKernel:
    def test_matches_xla_solve_with_gram(self, rng):
        n, r = 700, 10
        a, b, n_reg = _spd_batch(rng, n, r)
        g = rng.normal(size=(40, r)).astype(np.float32)
        gram = jnp.asarray(g.T @ g * 0.01)
        eye = jnp.eye(r, dtype=jnp.float32)
        ref = als_ops.regularized_solve(a, b, n_reg, 0.1, eye, gram)
        out = solve_normal_eq_pallas(a, b, n_reg, 0.1, gram, interpret=True)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=2e-5
        )
        # empty rows (n_reg == 0) masked to exact zeros on both paths
        zero_rows = np.asarray(n_reg) == 0
        assert (np.asarray(out)[zero_rows] == 0).all()

    def test_matches_xla_solve_no_gram(self, rng):
        n, r = 300, 10
        a, b, n_reg = _spd_batch(rng, n, r)
        eye = jnp.eye(r, dtype=jnp.float32)
        ref = als_ops.regularized_solve(a, b, n_reg, 0.5, eye, None)
        out = solve_normal_eq_pallas(a, b, n_reg, 0.5, None, interpret=True)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=2e-5
        )

    @pytest.mark.parametrize("r", [1, 3, 32])
    def test_rank_edges(self, rng, r):
        a, b, n_reg = _spd_batch(rng, 40, r)
        eye = jnp.eye(r, dtype=jnp.float32)
        ref = als_ops.regularized_solve(a, b, n_reg, 0.5, eye, None)
        out = solve_normal_eq_pallas(a, b, n_reg, 0.5, None, interpret=True)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=5e-5
        )

    def test_rank_bound_raises(self, rng):
        r = 33
        a, b, n_reg = _spd_batch(rng, 8, r)
        with pytest.raises(ValueError, match="rank"):
            solve_normal_eq_pallas(a, b, n_reg, 0.5, None, interpret=True)
        assert not pallas_solve_preferred(r)
        assert pallas_solve_preferred(10)

    def test_factor_gram_tiers(self, rng):
        f = jnp.asarray(rng.normal(size=(777, 10)).astype(np.float32))
        ref = psn.pdot(f.T, f)
        out = factor_gram_pallas(f, interpret=True)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), rtol=1e-6, atol=1e-3
        )
        for mode, rtol in (("high", 1e-4), ("default", 2e-2)):
            out_t = factor_gram_pallas(f, mode=mode, interpret=True)
            np.testing.assert_allclose(
                np.asarray(ref), np.asarray(out_t), rtol=rtol, atol=1e-1
            )

    def test_full_runner_parity_grouped_implicit(self, rng):
        """The whole ALS loop with the Pallas solve (interpret leg) stays
        within fp tolerance of the XLA-solve loop — the tier-1 proof that
        the fused consumer is a drop-in for every runner."""
        nu, ni, nnz, r = 300, 200, 4000, 8
        u = rng.integers(0, nu, nnz).astype(np.int64)
        i = rng.integers(0, ni, nnz).astype(np.int64)
        c = (rng.random(nnz) * 4 + 1).astype(np.float32)
        x0 = jnp.asarray((rng.normal(size=(nu, r)) * 0.1).astype(np.float32))
        y0 = jnp.asarray((rng.normal(size=(ni, r)) * 0.1).astype(np.float32))
        by_u = tuple(
            jnp.asarray(a) for a in als_ops.build_grouped_edges(u, i, c, nu)
        )
        by_i = tuple(
            jnp.asarray(a) for a in als_ops.build_grouped_edges(i, u, c, ni)
        )
        xa, ya = als_ops.als_run_grouped(
            *by_u, *by_i, x0, y0, nu, ni, 5, 0.1, 40.0, True,
            solve_kernel="xla",
        )
        xb, yb = als_ops.als_run_grouped(
            *by_u, *by_i, x0, y0, nu, ni, 5, 0.1, 40.0, True,
            solve_kernel="pallas_interpret",
        )
        np.testing.assert_allclose(
            np.asarray(xa), np.asarray(xb), atol=5e-4
        )
        np.testing.assert_allclose(
            np.asarray(ya), np.asarray(yb), atol=5e-4
        )

    def test_full_runner_parity_explicit_coo(self, rng):
        nu, ni, nnz, r = 200, 150, 3000, 6
        u = rng.integers(0, nu, nnz).astype(np.int32)
        i = rng.integers(0, ni, nnz).astype(np.int32)
        c = (rng.random(nnz) * 4 + 1).astype(np.float32)
        pad = (-nnz) % 2048
        uj = jnp.asarray(np.pad(u, (0, pad)))
        ij = jnp.asarray(np.pad(i, (0, pad)))
        rj = jnp.asarray(np.pad(c, (0, pad)))
        vj = jnp.asarray(np.pad(np.ones(nnz, np.float32), (0, pad)))
        x0 = jnp.asarray((rng.normal(size=(nu, r)) * 0.1).astype(np.float32))
        y0 = jnp.asarray((rng.normal(size=(ni, r)) * 0.1).astype(np.float32))
        xa, _ = als_ops.als_explicit_run(
            uj, ij, rj, vj, x0, y0, nu, ni, 4, 0.1, solve_kernel="xla"
        )
        xb, _ = als_ops.als_explicit_run(
            uj, ij, rj, vj, x0, y0, nu, ni, 4, 0.1,
            solve_kernel="pallas_interpret",
        )
        np.testing.assert_allclose(
            np.asarray(xa), np.asarray(xb), atol=5e-4
        )

    def test_resolve_solve_kernel(self):
        # CPU backend: auto resolves to the XLA path; typo raises
        assert als_ops.resolve_solve_kernel(10, np.float32) == "xla"
        set_config(als_solve_kernel="nope")
        with pytest.raises(ValueError, match="als_solve_kernel"):
            als_ops.resolve_solve_kernel(10, np.float32)


# ---------------------------------------------------------------------------
# K-Means single-shot padding (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


class TestSingleShotPaddingJitted:
    def test_second_call_compiles_nothing(self, rng):
        """lloyd_accumulate_walk pads INSIDE its one jitted program: a
        repeat call with the same signature must hit jit's executable
        cache — zero new XLA backend compiles (eager padding would
        dispatch ~6 ops per call that the cache could not see)."""
        from oap_mllib_tpu.ops.pallas.kmeans_kernel import (
            lloyd_accumulate_walk,
        )

        n, d, k = 333, 5, 3
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.ones((n,), jnp.float32)
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = lloyd_accumulate_walk(x, w, c, interpret=True)
        np.asarray(s1)
        before = progcache.xla_compile_count()
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c, interpret=True)
        np.asarray(s2)
        assert progcache.xla_compile_count() - before == 0
        assert np.array_equal(np.asarray(s1), np.asarray(s2))


class TestWalkColumnLayout:
    """The walks' per-row column (K-Means weights, PCA mask) rides
    lane-dense, because the chip's compiler refuses a ``(tile_rows, 1)``
    DMA window on an ``(n, 1)`` operand (tests/test_tpu_compile.py
    compiles the real thing; this pins the layout's arithmetic)."""

    @pytest.mark.parametrize("tile_rows", [128, 256, 512, 1024])
    def test_lane_dense_then_column_is_the_identity(self, rng, tile_rows):
        from oap_mllib_tpu.ops.pallas import _dbuf

        n = 3 * tile_rows
        col = jnp.asarray(rng.random((n, 1)).astype(np.float32))
        dense = _dbuf.lane_dense(col, tile_rows)
        assert dense.shape == (3, tile_rows // 128, 128)
        # row i of tile t sits at [t, i // 128, i % 128]
        assert float(dense[2, 1 % (tile_rows // 128), 5]) == float(
            col[2 * tile_rows + (1 % (tile_rows // 128)) * 128 + 5, 0]
        )
        for t in range(3):
            back = _dbuf.column(dense[t])
            assert back.shape == (tile_rows, 1)
            assert np.array_equal(
                np.asarray(back),
                np.asarray(col[t * tile_rows : (t + 1) * tile_rows]),
            )

    @pytest.mark.parametrize("bad", [0, 64, 100, 513, -128])
    def test_tile_rows_must_be_whole_lane_groups(self, bad):
        from oap_mllib_tpu.ops.pallas import _dbuf

        with pytest.raises(ValueError, match="multiple of 128"):
            _dbuf.check_tile_rows(bad)

    def test_walk_rejects_a_misaligned_tile_before_tracing(self, rng):
        from oap_mllib_tpu.ops.pallas.kmeans_kernel import (
            lloyd_accumulate_walk,
        )

        x = jnp.asarray(rng.normal(size=(300, 8)).astype(np.float32))
        with pytest.raises(ValueError, match="multiple of 128"):
            lloyd_accumulate_walk(
                x, jnp.ones((300,), jnp.float32), x[:3], interpret=True,
                tile_rows=200, depth=2,
            )

    def test_als_moment_sheet_pads_to_the_sublane_tile(self, rng):
        """111 rows at rank 10 -> 112: the column walk DMAs whole-height
        windows and Mosaic wants their row extent in eights; the solve
        must not notice."""
        n, r = 300, 10
        mm = rng.normal(size=(n, r, r)).astype(np.float32)
        a = jnp.asarray(np.einsum("nij,nkj->nik", mm, mm) + 0.5 * np.eye(r))
        b = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
        n_reg = jnp.asarray(np.full((n,), 3.0, np.float32))
        ref = als_ops.regularized_solve(
            a, b, n_reg, 0.1, jnp.eye(r, dtype=jnp.float32)
        )
        for depth in (0, 2):
            out = solve_normal_eq_pallas(
                a, b, n_reg, 0.1, interpret=True, depth=depth
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=1e-4
            )


class TestDispatchBounds:
    """The shape rules admit exactly what the chip's compiler accepted
    under the plane's scoped-VMEM ceiling (tests/test_tpu_compile.py
    compiles the edges)."""

    @pytest.mark.parametrize("k,d,ok", [
        (1000, 256, True),     # the headline shape
        (4096, 512, True),     # on the k*d bound
        (2048, 1024, True),
        (512, 4096, True),
        (4096, 513, False),    # d pads to 640: past the k*d bound
        (4097, 128, False),    # k pads to 4224: past the k bound
        (16384, 128, False),   # within k*d, but (tile, k) temporaries
        (128, 4224, False),    # past the d bound
    ])
    def test_kmeans_rule(self, k, d, ok):
        from oap_mllib_tpu.ops.kmeans_ops import pallas_preferred

        for tier in ("highest", "high", "default"):
            assert pallas_preferred(d, k, tier) is ok

    def test_pca_rule_and_typo(self):
        from oap_mllib_tpu.ops.pallas.pca_kernel import pallas_gram_preferred

        assert pallas_gram_preferred(128, "highest")
        assert pallas_gram_preferred(2048, "high")
        assert not pallas_gram_preferred(2049, "high")
        assert not pallas_gram_preferred(128, "hi")  # not a tier

    def test_compiled_launches_carry_the_vmem_ceiling(self):
        from oap_mllib_tpu.ops.pallas import _tiers

        params = _tiers.compiled_kwargs(
            False, vmem_limit_bytes=_tiers.VMEM_LIMIT_BYTES,
            has_side_effects=True,
        )["compiler_params"]
        assert params.vmem_limit_bytes == _tiers.VMEM_LIMIT_BYTES
        assert params.has_side_effects
        assert _tiers.compiled_kwargs(True, has_side_effects=True) == {}
        # under the v5e core's 128 MiB, well over the 16 MiB default
        assert 16 << 20 < _tiers.VMEM_LIMIT_BYTES < 128 << 20
