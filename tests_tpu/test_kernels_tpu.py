"""Compiled-mode TPU legs for the kernel plane: Mosaic-lowered PCA
moments + ALS solve parity, the double-buffered walks every default fit
launches against the grid kernels they share their tile bodies with,
the remote-DMA ring kernel vs the psum reference on the real mesh, and
the ring's overlap-efficiency bound.

Every test skips unless the session backend is a TPU — see conftest.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from oap_mllib_tpu.ops import als_ops
from oap_mllib_tpu.ops.pallas.als_kernel import solve_normal_eq_pallas
from oap_mllib_tpu.ops.pallas.pca_kernel import covariance_pallas
from oap_mllib_tpu.ops.pallas.ring_reduce import ring_allreduce
from oap_mllib_tpu.ops.pca_ops import _covariance_jit
from oap_mllib_tpu.utils.jax_compat import shard_map


class TestPcaKernelCompiled:
    def test_covariance_compiled_matches_xla(self, rng):
        n, d = 4096, 96
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) + 3.0)
        m = jnp.asarray((rng.random(n) < 0.9).astype(np.float32))
        nv = jnp.asarray(float(np.asarray(m).sum()))
        cov_p, mean_p = covariance_pallas(x, m, nv)  # interpret=False
        cov_r, mean_r = _covariance_jit(x, m, nv)
        np.testing.assert_allclose(
            np.asarray(mean_p), np.asarray(mean_r), atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(cov_p), np.asarray(cov_r), atol=1e-4
        )

    @pytest.mark.parametrize("mode,atol", [("high", 1e-3), ("default", 5e-2)])
    def test_split_tiers_compiled(self, rng, mode, atol):
        n, d = 2048, 64
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        m = jnp.ones((n,), jnp.float32)
        nv = jnp.asarray(float(n))
        cov_t, _ = covariance_pallas(x, m, nv, mode=mode)
        cov_r, _ = _covariance_jit(x, m, nv)
        np.testing.assert_allclose(
            np.asarray(cov_t), np.asarray(cov_r), atol=atol
        )


class TestAlsSolveCompiled:
    def test_solve_compiled_matches_xla(self, rng):
        n, r = 4096, 10
        m = rng.normal(size=(n, r, r)).astype(np.float32)
        a = jnp.asarray(
            np.einsum("nij,nkj->nik", m, m) + 0.5 * np.eye(r)
        )
        b = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
        n_reg = jnp.asarray(rng.integers(0, 40, n).astype(np.float32))
        g = rng.normal(size=(64, r)).astype(np.float32)
        gram = jnp.asarray(g.T @ g * 0.01)
        eye = jnp.eye(r, dtype=jnp.float32)
        ref = als_ops.regularized_solve(a, b, n_reg, 0.1, eye, gram)
        out = solve_normal_eq_pallas(a, b, n_reg, 0.1, gram)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(out), atol=1e-4
        )


class TestWalkKernelsCompiled:
    """The rotating-buffer walks (autotune.DEFAULTS: depth 2).  K-Means
    has the walk alone: it is held bit for bit across rotation depths
    and, at ``highest``, to its schedule-identical XLA scan
    (``_xla_walk``) and to the XLA accumulate.  PCA's and ALS's walks
    are held to their grid kernels at the same row partition — both
    compile the same tile body, so on one chip the results agree bit for
    bit.  Per-row weights that all differ make any slip in the walk's
    lane-dense column layout (``_dbuf.lane_dense`` / ``_dbuf.column``)
    show."""

    @pytest.mark.parametrize("mode", ["highest", "high", "default"])
    def test_kmeans_walk_matches_its_xla_twin(self, rng, mode):
        from oap_mllib_tpu.ops.kmeans_ops import _accumulate
        from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk

        n, d, k = 5000, 100, 37  # 10 tiles of 512, the last one padded
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        walk = kk.lloyd_accumulate_walk(x, w, c, mode=mode, tile_rows=512)
        # rotation depth moves the overlap, never a bit
        deeper = kk.lloyd_accumulate_walk(
            x, w, c, mode=mode, tile_rows=512, depth=3
        )
        for a, b in zip(deeper, walk):
            assert np.array_equal(np.asarray(a), np.asarray(b)), mode
        if mode != "highest":
            # the fast tiers rank on a bf16 sheet: Mosaic and XLA may
            # break a near-tie differently (their Lloyd-level parity is
            # test_kmeans_tpu.py's)
            return
        # the same tiles in the same order through the same tile body,
        # compiled by XLA: f32 rounding of the two compilers' reductions.
        # XLA:TPU keeps "excess precision" by default — a convert to
        # bf16 and back leaves the f32 as it was — which makes the mid
        # and lo parts of the sums' exact split zero (the twin then sums
        # bf16(w*x), off by 4e-4 of the sums); Mosaic rounds as written.
        # The twin is the CPU's program; here it is held to what it says.
        def twin_of(x, w, c):
            padded = kk._pad_operands_traced(x, w, c, block_rows=512)
            return kk._xla_walk(
                *padded, "highest", True, 512, kk.live_tiles(padded[1], 512)
            )

        twin = jax.jit(twin_of).lower(x, w, c).compile(
            compiler_options={"xla_allow_excess_precision": False}
        )(x, w, c)
        refs = [
            (twin[0][:k, :d], twin[1][0, :k], twin[2][0, 0]),
            _accumulate(x, w, c),
            # another partition reorders the f32 tile reduction only
            kk.lloyd_accumulate_walk(x, w, c, tile_rows=256),
        ]
        for s1, c1, t1 in refs:
            np.testing.assert_allclose(
                np.asarray(s1), np.asarray(walk[0]), atol=1e-3
            )
            np.testing.assert_allclose(
                np.asarray(c1), np.asarray(walk[1]), atol=1e-3
            )
            np.testing.assert_allclose(
                float(t1), float(walk[2]), rtol=1e-5
            )

    @pytest.mark.parametrize("need_gram", [True, False])
    def test_pca_walk_matches_grid_kernel(self, rng, need_gram):
        from oap_mllib_tpu.ops.pallas.pca_kernel import pca_moments_pallas

        n, d = 5000, 96
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) + 3.0)
        m = jnp.asarray((rng.random(n) + 0.25).astype(np.float32))
        mean = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
        grid = pca_moments_pallas(x, m, mean, need_gram=need_gram)
        walk = pca_moments_pallas(
            x, m, mean, need_gram=need_gram, tile_rows=512, depth=2
        )
        for a, b in zip(walk, grid):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_pca_covariance_walk_matches_xla(self, rng):
        n, d = 4096, 96
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) + 3.0)
        m = jnp.asarray((rng.random(n) < 0.9).astype(np.float32))
        nv = jnp.asarray(float(np.asarray(m).sum()))
        cov_p, mean_p = covariance_pallas(x, m, nv, tile_rows=512, depth=2)
        cov_r, mean_r = _covariance_jit(x, m, nv)
        np.testing.assert_allclose(
            np.asarray(mean_p), np.asarray(mean_r), atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(cov_p), np.asarray(cov_r), atol=1e-4
        )

    def test_als_walks_match_grid_kernels(self, rng):
        from oap_mllib_tpu.ops.pallas.als_kernel import factor_gram_pallas

        n, r = 4096, 10
        mm = rng.normal(size=(n, r, r)).astype(np.float32)
        a = jnp.asarray(np.einsum("nij,nkj->nik", mm, mm) + 0.5 * np.eye(r))
        b = jnp.asarray(rng.normal(size=(n, r)).astype(np.float32))
        n_reg = jnp.asarray(rng.integers(0, 40, n).astype(np.float32))
        g = rng.normal(size=(64, r)).astype(np.float32)
        gram = jnp.asarray(g.T @ g * 0.01)
        grid = solve_normal_eq_pallas(a, b, n_reg, 0.1, gram)
        walk = solve_normal_eq_pallas(
            a, b, n_reg, 0.1, gram, batch=256, depth=2
        )
        assert np.array_equal(np.asarray(walk), np.asarray(grid))
        f = jnp.asarray(rng.normal(size=(3706, r)).astype(np.float32))
        assert np.array_equal(
            np.asarray(factor_gram_pallas(f, tile_rows=512, depth=2)),
            np.asarray(factor_gram_pallas(f)),
        )


@pytest.fixture
def ring_mesh():
    n = len(jax.devices())
    if n < 2:
        pytest.skip("ring kernel needs >= 2 TPU devices")
    return jax.make_mesh((n,), ("data",)), n


class TestRingCompiled:
    def _run(self, mesh, world, g, interpret=False):
        gd = jax.device_put(
            jnp.asarray(g), NamedSharding(mesh, P("data", None, None))
        )
        fn = jax.jit(
            shard_map(
                lambda b: ring_allreduce(
                    b[0], "data", world, interpret=interpret
                )[None],
                mesh=mesh, in_specs=P("data", None, None),
                out_specs=P("data", None, None), check_vma=False,
            )
        )
        return np.asarray(fn(gd))

    def test_remote_dma_ring_matches_psum_reference(self, rng, ring_mesh):
        """The acceptance bound on hardware: the Mosaic remote-DMA ring
        vs the ppermute parity schedule (identical segment order) and
        the plain sum, at 1e-5."""
        mesh, world = ring_mesh
        g = rng.normal(size=(world, 1000, 384)).astype(np.float32)
        out_dma = self._run(mesh, world, g, interpret=False)
        out_ref = self._run(mesh, world, g, interpret=True)  # ppermute
        scale = np.abs(g.sum(0)).max()
        np.testing.assert_allclose(
            out_dma[0], g.sum(0), rtol=1e-5, atol=1e-5 * scale
        )
        # same schedule -> bit-identical across the two backends
        np.testing.assert_allclose(
            out_dma[0], out_ref[0], rtol=1e-6, atol=1e-6 * scale
        )
        for i in range(1, world):
            assert np.array_equal(out_dma[0], out_dma[i])

    def test_ring_overlap_efficiency(self, rng, ring_mesh):
        """Overlap-efficiency leg: the ring-fused model-sharded Lloyd
        pass must not be slower than the psum path (the bi-directional
        DMA ring drives both ICI links while the VPU folds; a regression
        here means the overlap broke even if parity still holds)."""
        import time

        from oap_mllib_tpu.config import set_config
        from oap_mllib_tpu.ops import kmeans_ops
        from oap_mllib_tpu.parallel.mesh import get_mesh

        mesh, world = ring_mesh
        n, d, k = 1 << 17, 256, 256
        data = rng.normal(size=(n, d)).astype(np.float32)
        w = np.ones((n,), np.float32)
        c0 = data[:k]
        m = get_mesh()
        xs = jax.device_put(
            jnp.asarray(data), NamedSharding(m, P("data", "model"))
        )
        ws = jax.device_put(jnp.asarray(w), NamedSharding(m, P("data")))
        tol = jnp.asarray(0.0, jnp.float32)

        def wall(iters=24):
            r = kmeans_ops.lloyd_run_model_sharded(
                xs, ws, jnp.asarray(c0), iters, tol, m, "data", "model"
            )
            np.asarray(r[0])  # block
            t0 = time.perf_counter()
            r = kmeans_ops.lloyd_run_model_sharded(
                xs, ws, jnp.asarray(c0), iters, tol, m, "data", "model"
            )
            np.asarray(r[0])
            return time.perf_counter() - t0

        t_ring = wall()
        set_config(ring_reduction="off")
        t_psum = wall()
        set_config(ring_reduction="auto")
        # generous bound: the fused ring must at least break even
        assert t_ring <= t_psum * 1.25, (t_ring, t_psum)
