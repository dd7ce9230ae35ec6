"""Compiled-mode TPU tests: Mosaic-lowered Pallas kernels + precision tiers.

Round-1 gap: every Pallas assertion ran interpret-only, so
a Mosaic lowering regression would ship green.  These tests compile the
fused kernel for the real chip and hold it to the XLA path's results, and
pin the "high" (bf16_3x) tier inside the 1e-4 parity envelope.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oap_mllib_tpu.ops.kmeans_ops import _accumulate, lloyd_run
from oap_mllib_tpu.ops.pallas.kmeans_kernel import lloyd_accumulate_walk


def _walk_launches(model):
    """Dispatches of the fused Lloyd walk that the fit's ``lloyd_loop``
    span booked (ops/pallas/_tiers.kernel_launch): counted, not inferred
    from the configuration."""
    loop = model.summary.timings.root.node("lloyd_loop")
    return loop.attrs.get("kernels", {}).get("kmeans.lloyd_loop", 0)


class TestPallasCompiled:
    def test_accumulate_compiled_matches_xla(self, rng):
        n, d, k = 4096, 100, 37
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = _accumulate(x, w, c)
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c)  # interpret=False
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-3)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-3)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-5)

    def test_lloyd_loop_compiled(self, rng):
        n, d, k = 8192, 32, 16
        x = rng.normal(size=(n, d)).astype(np.float32)
        init = x[rng.choice(n, k, replace=False)]
        xj, wj = jnp.asarray(x), jnp.ones((n,), jnp.float32)
        cj = jnp.asarray(init)
        tol = jnp.asarray(1e-6, jnp.float32)
        c1, i1, t1, _ = lloyd_run(xj, wj, cj, 20, tol)
        c2, i2, t2, _ = lloyd_run(xj, wj, cj, 20, tol, accumulate="pallas")
        assert int(i1) == int(i2)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-3)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-3)

    @pytest.mark.parametrize("mode,bound", [("high", 1e-4), ("default", 5e-3)])
    def test_fast_tiers_compiled_within_parity(self, rng, mode, bound):
        """Fast tiers on blob-like data: "high" centers within the 1e-4
        parity bar; "default" (single-pass all-bf16 sums) within the XLA
        default tier's ~1e-3-relative envelope."""
        n, d, k = 16384, 64, 32
        proto = rng.normal(size=(k, d)).astype(np.float32)
        x = proto[rng.integers(k, size=n)] + 0.1 * rng.normal(size=(n, d)).astype(
            np.float32
        )
        init = proto + 0.01 * rng.normal(size=(k, d)).astype(np.float32)
        xj, wj = jnp.asarray(x), jnp.ones((n,), jnp.float32)
        cj = jnp.asarray(init)
        tol = jnp.asarray(0.0, jnp.float32)
        c1, _, t1, _ = lloyd_run(xj, wj, cj, 5, tol)
        c2, _, t2, _ = lloyd_run(
            xj, wj, cj, 5, tol, precision=mode, accumulate="pallas"
        )
        scale = float(jnp.max(jnp.abs(c1)))
        assert float(jnp.max(jnp.abs(c1 - c2))) / scale < bound
        assert abs(float(t1) - float(t2)) / float(t1) < bound


class TestXlaPrecisionTiers:
    def test_high_tier_within_parity(self, rng):
        """XLA "high" (bf16_3x) vs "highest" on blob data: 1e-4 envelope
        (round-1 measured 6.6e-5 cost error at bench scale)."""
        n, d, k = 16384, 64, 32
        proto = rng.normal(size=(k, d)).astype(np.float32)
        x = proto[rng.integers(k, size=n)] + 0.1 * rng.normal(size=(n, d)).astype(
            np.float32
        )
        init = proto + 0.01 * rng.normal(size=(k, d)).astype(np.float32)
        xj, wj = jnp.asarray(x), jnp.ones((n,), jnp.float32)
        cj = jnp.asarray(init)
        tol = jnp.asarray(0.0, jnp.float32)
        c1, _, t1, _ = lloyd_run(xj, wj, cj, 5, tol, 1, "highest")
        c2, _, t2, _ = lloyd_run(xj, wj, cj, 5, tol, 1, "high")
        scale = float(jnp.max(jnp.abs(c1)))
        assert float(jnp.max(jnp.abs(c1 - c2))) / scale < 1e-4
        assert abs(float(t1) - float(t2)) / float(t1) < 1e-4

    def test_auto_picks_pallas_for_deep_features(self, rng):
        """kmeans_kernel=auto routes every tier whose blocks fit VMEM to
        the fused kernel (kmeans_ops.pallas_preferred) — verified by
        counting its dispatches, not inferred."""
        from oap_mllib_tpu.config import set_config
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(kmeans_kernel="auto", matmul_precision="high")
        try:
            x = rng.normal(size=(2048, 256)).astype(np.float32)
            m = KMeans(k=8, max_iter=5, seed=1).fit(x)
            assert m.summary.accelerated
            assert _walk_launches(m) == 1, (
                "auto did not pick pallas for d=256 at high tier"
            )
        finally:
            set_config(matmul_precision="highest")

    def test_estimator_pallas_kernel_config(self, rng):
        """KMeans(kmeans_kernel=pallas) runs the fused kernel end-to-end —
        verified by counting its dispatches, not inferred."""
        from oap_mllib_tpu.config import set_config
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(kmeans_kernel="pallas")
        try:
            x = rng.normal(size=(2048, 16)).astype(np.float32)
            m = KMeans(k=4, max_iter=10, seed=1).fit(x)
            assert m.summary.accelerated
            assert _walk_launches(m) == 1, (
                "pallas kernel was configured but never dispatched"
            )
            assert m.summary.kernel == "pallas"
            # auto prices the "default" tier ON Pallas too
            # (kmeans_ops.pallas_preferred): the walk again, and a cost
            # inside that tier's envelope of the f32 fit
            set_config(kmeans_kernel="auto", matmul_precision="default")
            m2 = KMeans(k=4, max_iter=10, seed=1).fit(x)
            assert _walk_launches(m2) == 1 and m2.summary.kernel == "pallas"
            np.testing.assert_allclose(
                m.summary.training_cost, m2.summary.training_cost, rtol=1e-2
            )
            # xla forces the chunked XLA Lloyd — no walk dispatched
            set_config(kmeans_kernel="xla", matmul_precision="highest")
            m3 = KMeans(k=4, max_iter=10, seed=1).fit(x)
            assert _walk_launches(m3) == 0 and m3.summary.kernel == "xla"
        finally:
            set_config(kmeans_kernel="auto", matmul_precision="highest")


class TestTheWalkEndsAtTheLastRow:
    """The Lloyd walk's trip count is read on the device from the weights
    (``kmeans_kernel.live_tiles``): a fit whose table does not fill its
    x2 bucket walks only the tiles that hold a row.  Every tile past the
    bound would add exact zeros, so centres, counts, cost and
    ``num_iter`` must equal a walk over EVERY tile bit for bit — the
    parent's program, rebuilt here by pinning the bound to the tile
    count.  The cell's table (3,125,000 float64 rows on the 4,194,304
    bucket: 6104 of 8192 tiles) and an on-bucket one (every tile live)."""

    @pytest.mark.parametrize("rows,dtype,tiles", [
        (3_125_000, np.float64, (6104, 8192)),
        (1_048_576, np.float32, (2048, 2048)),
    ], ids=["f64rows_off_bucket", "f32_on_bucket"])
    def test_fit_equals_the_full_walk_bit_for_bit(self, monkeypatch, rows,
                                                  dtype, tiles):
        import hashlib

        from oap_mllib_tpu.models.kmeans import KMeans
        from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk
        from oap_mllib_tpu.utils import progcache

        d, k = 256, 1000
        rng = np.random.default_rng(rows)
        proto = 4.0 * rng.standard_normal((k, d), dtype=np.float32)
        x = np.empty((rows, d), dtype)
        for lo in range(0, rows, 1 << 18):  # in pieces: no second table
            hi = min(lo + (1 << 18), rows)
            x[lo:hi] = proto[rng.integers(k, size=hi - lo)]
            x[lo:hi] += 0.3 * rng.standard_normal((hi - lo, d), dtype=np.float32)

        def fit():
            m = KMeans(k=k, max_iter=6, tol=0.0, seed=11,
                       init_mode="random").fit(x)
            assert m.summary.kernel == "pallas"
            loop = m.summary.timings.root.node("lloyd_loop")
            return m, (loop.attrs["walk_tiles_live"], loop.attrs["walk_tiles"])

        bounded, walked = fit()
        assert walked == tiles
        # the parent's walk: every tile, whatever the weights say
        monkeypatch.setattr(
            kk, "live_tiles",
            lambda w_p, tile_rows: jnp.int32(w_p.shape[0] // tile_rows),
        )
        progcache.clear()  # the registry holds the bounded program
        whole, walked_whole = fit()
        assert walked_whole == (tiles[1], tiles[1])
        digests = []
        for m in (bounded, whole):
            s = m.summary
            parts = (
                np.asarray(m.cluster_centers_), np.asarray(s.cluster_sizes),
                np.float64(s.training_cost), np.int64(s.num_iter),
            )
            digests.append(hashlib.sha256(
                b"".join(np.ascontiguousarray(p).tobytes() for p in parts)
            ).hexdigest())
        print(
            f"walk bound {rows}x{d} {np.dtype(dtype).name}: tiles {walked} "
            f"vs {walked_whole}, num_iter {bounded.summary.num_iter}, cost "
            f"{bounded.summary.training_cost!r} vs "
            f"{whole.summary.training_cost!r}, sha256 {digests[0][:16]} vs "
            f"{digests[1][:16]}"
        )
        assert bounded.summary.num_iter == whole.summary.num_iter == 6
        assert float(np.sum(bounded.summary.cluster_sizes)) == rows
        assert digests[0] == digests[1]


class TestAssignmentIsF32:
    """At ``highest`` the XLA assignment names the f32 nearest centre.
    ``jnp.argmin`` did not on this compiler (its reduction's value output
    is typed bfloat16; 1 served row in 4096 went to a centre 1.5e-2
    farther), which is why the programs use ``kmeans_ops.argmin_rows``.
    Centres come in pairs 0.02 apart, so each row has two candidates
    whose d2 differ by ~0.008 near 23 — far inside bfloat16's step of
    0.125, far outside f32 rounding.  A pair sits either side by side
    (one 128-lane tile) or 500 ids apart (always two tiles)."""

    @pytest.mark.parametrize("rows", [1024, 4096])
    @pytest.mark.parametrize("pairs", ["adjacent", "split"])
    @pytest.mark.parametrize("sheet", ["d2", "half-score"])
    def test_argmin_rows_is_the_float64_nearest(self, rng, sheet, pairs,
                                                rows):
        from oap_mllib_tpu.ops import kmeans_ops
        from oap_mllib_tpu.utils import precision as psn

        d, k = 256, 1000
        proto = rng.standard_normal((k // 2, d), dtype=np.float32)
        delta = rng.standard_normal((k // 2, d), dtype=np.float32)
        delta *= 0.02 / np.linalg.norm(delta, axis=1, keepdims=True)
        c = np.empty((k, d), np.float32)
        if pairs == "adjacent":
            c[0::2], c[1::2] = proto, proto + delta
        else:
            c[: k // 2], c[k // 2:] = proto, proto + delta
        x = proto[rng.integers(k // 2, size=rows)] + 0.3 * rng.standard_normal(
            (rows, d), dtype=np.float32
        )

        def ids_of(xb, cb):
            if sheet == "d2":
                s = kmeans_ops.pairwise_sq_dists(xb, cb)
            else:  # the Lloyd loop body's ranking sheet
                s = 0.5 * jnp.sum(cb * cb, axis=1)[None, :] - psn.pdot(xb, cb.T)
            return kmeans_ops.argmin_rows(s)

        ids = np.asarray(jax.jit(ids_of)(jnp.asarray(x), jnp.asarray(c)))
        x64, c64 = x.astype(np.float64), c.astype(np.float64)
        x_sq, c_sq = (x64 * x64).sum(1), (c64 * c64).sum(1)
        d2 = x_sq[:, None] + c_sq[None, :] - 2.0 * x64 @ c64.T
        excess = d2[np.arange(rows), ids] - d2.min(axis=1)
        # what f32 rounding of |x|^2 + |c|^2 - 2 x.c can explain
        bound = 8 * np.finfo(np.float32).eps * (x_sq.max() + c_sq.max())
        assert excess.max() <= bound, (excess.max(), bound)
