"""Pallas fused-kernel tests (interpret mode on the CPU pseudo-cluster).

Compiled-mode (non-interpret) coverage on real TPU hardware lives in
``tests_tpu/`` — run by dev/ci.sh whenever a TPU backend is present — so a
Mosaic lowering regression cannot ship green on the CPU suite alone.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from oap_mllib_tpu.ops.kmeans_ops import _accumulate, lloyd_run
from oap_mllib_tpu.ops.pallas.kmeans_kernel import lloyd_accumulate_walk


class TestFusedAccumulate:
    def test_matches_xla_accumulate(self, rng):
        n, d, k = 700, 20, 7
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray((rng.random(n) < 0.9).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = _accumulate(x, w, c)
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c, interpret=True)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=0)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-5)

    def test_weighted_rows(self, rng):
        n, d, k = 600, 8, 3
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray(rng.random(n).astype(np.float32))  # fractional weights
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = _accumulate(x, w, c)
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c, interpret=True)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)

    @pytest.mark.parametrize("mode,sums_atol", [("high", 5e-3), ("default", 2e-1)])
    def test_fast_tiers_close(self, rng, mode, sums_atol):
        """bf16 tiers: "high" sums stay ~f32-exact via the hi/lo split (the
        one-hot is exactly representable); "default" is single-pass all
        -bf16 — the XLA default tier's ~1e-3-relative envelope.  Distances
        may flip near-ties only."""
        n, d, k = 640, 24, 9
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, t1 = _accumulate(x, w, c)
        s2, c2, t2 = lloyd_accumulate_walk(x, w, c, mode=mode, interpret=True)
        # well-separated random clusters: assignments identical
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-3)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=sums_atol)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-3)

    def test_bad_mode_raises(self, rng):
        x = jnp.zeros((8, 4), jnp.float32)
        w = jnp.ones((8,), jnp.float32)
        c = jnp.zeros((2, 4), jnp.float32)
        with pytest.raises(ValueError, match="mode"):
            lloyd_accumulate_walk(x, w, c, mode="fast", interpret=True)

    def test_unaligned_shapes_padded(self, rng):
        """n, k, d all unaligned to blocks/lanes: padding must be invisible."""
        n, d, k = 333, 5, 3
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.ones((n,), jnp.float32)
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        s1, c1, _ = _accumulate(x, w, c)
        s2, c2, _ = lloyd_accumulate_walk(x, w, c, interpret=True)
        assert float(jnp.sum(c2)) == n  # no row lost to padding
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


class TestFusedLloydLoop:
    def test_matches_xla_lloyd(self, rng):
        n, d, k = 640, 6, 4
        x = rng.normal(size=(n, d)).astype(np.float32)
        init = x[rng.choice(n, k, replace=False)]
        xj, wj = jnp.asarray(x), jnp.ones((n,), jnp.float32)
        cj = jnp.asarray(init)
        tol = jnp.asarray(1e-6, jnp.float32)
        c1, i1, t1, n1 = lloyd_run(xj, wj, cj, 25, tol)
        c2, i2, t2, n2 = lloyd_run(
            xj, wj, cj, 25, tol, accumulate="pallas", interpret=True
        )
        assert int(i1) == int(i2)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-3)
        np.testing.assert_allclose(float(t1), float(t2), rtol=1e-3)
        np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), atol=1e-5)
