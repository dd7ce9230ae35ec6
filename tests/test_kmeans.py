"""K-Means parity + behavior tests.

Modeled on the reference's IntelKMeansSuite (forked Spark estimator suite:
default params, param validation, fit/transform/summary, persistence) plus
the survey §4 takeaway: oracle-parity with absTol against independent
NumPy math, and cost-based (not center-exact) comparison for RNG-sensitive
init (survey §7.3).
"""

import numpy as np
import pytest

from oap_mllib_tpu import KMeans, KMeansModel
from oap_mllib_tpu.config import set_config


def _blobs(rng, n=600, d=8, k=4, spread=0.05):
    """Well-separated gaussian blobs with known centers."""
    centers = rng.normal(size=(k, d)) * 5.0
    assign = rng.integers(k, size=n)
    x = centers[assign] + rng.normal(size=(n, d)) * spread
    return x, centers, assign


def _oracle_lloyd(x, centers, max_iter=50, tol=1e-6):
    """Independent plain-NumPy Lloyd oracle (test-local, not framework code)."""
    c = centers.copy()
    for _ in range(max_iter):
        d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        a = d2.argmin(1)
        newc = np.stack(
            [x[a == j].mean(0) if np.any(a == j) else c[j] for j in range(len(c))]
        )
        if ((newc - c) ** 2).sum(1).max() <= tol * tol:
            c = newc
            break
        c = newc
    d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    return c, float(d2.min(1).sum())


class TestDefaults:
    def test_default_params(self):
        km = KMeans()
        assert km.k == 2
        assert km.max_iter == 20
        assert km.tol == 1e-4
        assert km.init_mode == "k-means||"
        assert km.distance_measure == "euclidean"

    def test_param_validation(self):
        with pytest.raises(ValueError):
            KMeans(k=0)
        with pytest.raises(ValueError):
            KMeans(max_iter=-1)
        with pytest.raises(ValueError):
            KMeans(init_mode="bogus")
        with pytest.raises(ValueError):
            KMeans(distance_measure="manhattan")
        with pytest.raises(ValueError):
            KMeans(init_steps=0)


class TestParity:
    def test_cost_matches_oracle_fixed_init(self, rng):
        """Same init => same converged centers/cost as the NumPy oracle."""
        x, true_centers, _ = _blobs(rng)
        k = 4
        init = x[rng.choice(len(x), k, replace=False)]

        import jax.numpy as jnp

        from oap_mllib_tpu.ops.kmeans_ops import lloyd_run

        xj = jnp.asarray(x, jnp.float32)
        w = jnp.ones((len(x),), jnp.float32)
        centers, n_iter, cost, _ = lloyd_run(
            xj, w, jnp.asarray(init, jnp.float32), 50, jnp.asarray(1e-6, jnp.float32)
        )
        oc, ocost = _oracle_lloyd(x, init)
        # sort both center sets for comparison
        order = np.lexsort(np.asarray(centers).T)
        oorder = np.lexsort(oc.T)
        np.testing.assert_allclose(
            np.asarray(centers)[order], oc[oorder], atol=1e-3, rtol=1e-3
        )
        assert abs(float(cost) - ocost) / max(ocost, 1e-9) < 1e-3

    def test_recovers_blob_centers(self, rng):
        x, true_centers, _ = _blobs(rng, n=2000, k=4)
        model = KMeans(k=4, max_iter=50, tol=1e-6, seed=7).fit(x)
        # every true center should be close to some learned center
        d = np.linalg.norm(
            true_centers[:, None, :] - model.cluster_centers_[None, :, :], axis=-1
        )
        assert d.min(axis=1).max() < 0.1

    def test_accelerated_vs_fallback_cost_parity(self, rng):
        """TPU path and fallback path converge to comparable cost."""
        x, _, _ = _blobs(rng, n=1000, k=3)
        m_acc = KMeans(k=3, max_iter=50, tol=1e-6, seed=3).fit(x)
        assert m_acc.summary.accelerated
        set_config(device="cpu")
        m_fb = KMeans(k=3, max_iter=50, tol=1e-6, seed=3).fit(x)
        assert not m_fb.summary.accelerated
        a, b = m_acc.summary.training_cost, m_fb.summary.training_cost
        assert abs(a - b) / max(b, 1e-9) < 0.05


class TestBehavior:
    def test_fit_predict_shapes(self, rng):
        x, _, _ = _blobs(rng)
        model = KMeans(k=4, seed=1).fit(x)
        assert model.cluster_centers_.shape == (4, x.shape[1])
        pred = model.predict(x)
        assert pred.shape == (len(x),)
        assert pred.min() >= 0 and pred.max() < 4

    def test_summary(self, rng):
        x, _, _ = _blobs(rng)
        model = KMeans(k=4, max_iter=30, seed=1).fit(x)
        s = model.summary
        assert s.num_iter >= 1 and s.num_iter <= 30
        assert s.training_cost >= 0
        assert s.timings.total() > 0

    def test_predict_consistent_with_centers(self, rng):
        x, _, _ = _blobs(rng)
        model = KMeans(k=4, seed=1).fit(x)
        d2 = ((x[:, None, :] - model.cluster_centers_[None, :, :]) ** 2).sum(-1)
        np.testing.assert_array_equal(model.predict(x), d2.argmin(1))

    def test_k_equals_one(self, rng):
        x, _, _ = _blobs(rng, k=2)
        model = KMeans(k=1, max_iter=10, seed=0).fit(x)
        np.testing.assert_allclose(
            model.cluster_centers_[0], x.mean(0), atol=1e-3, rtol=1e-3
        )

    def test_max_iter_zero_returns_init(self, rng):
        x, _, _ = _blobs(rng)
        model = KMeans(k=3, max_iter=0, init_mode="random", seed=5).fit(x)
        assert model.cluster_centers_.shape == (3, x.shape[1])

    def test_random_init_mode(self, rng):
        x, _, _ = _blobs(rng)
        model = KMeans(k=4, init_mode="random", seed=2, max_iter=50, tol=1e-6).fit(x)
        rand_cost = KMeans(
            k=4, max_iter=0, init_mode="random", seed=2
        ).fit(x).summary.training_cost
        assert model.summary.training_cost < rand_cost + 1e-6

    def test_weighted_fit(self, rng):
        """Row weights shift the k=1 center to the weighted mean."""
        x = np.array([[0.0, 0.0], [10.0, 10.0]])
        w = np.array([3.0, 1.0])
        model = KMeans(k=1, max_iter=5, seed=0).fit(x, sample_weight=w)
        np.testing.assert_allclose(model.cluster_centers_[0], [2.5, 2.5], atol=1e-4)

    def test_cosine_falls_back(self, rng):
        x, _, _ = _blobs(rng)
        x = np.abs(x) + 0.1
        model = KMeans(k=3, distance_measure="cosine", seed=1).fit(x)
        assert not model.summary.accelerated
        assert model.cluster_centers_.shape == (3, x.shape[1])

    def test_non2d_raises(self):
        with pytest.raises(ValueError):
            KMeans(k=2).fit(np.zeros((5,)))


class TestPersistence:
    def test_save_load_roundtrip(self, tmp_path, rng):
        x, _, _ = _blobs(rng)
        model = KMeans(k=4, seed=1).fit(x)
        p = str(tmp_path / "kmeans_model")
        model.save(p)
        loaded = KMeansModel.load(p)
        np.testing.assert_array_equal(loaded.cluster_centers_, model.cluster_centers_)
        assert loaded.distance_measure == model.distance_measure
        np.testing.assert_array_equal(loaded.predict(x), model.predict(x))


class TestSharding:
    def test_uneven_rows_padding(self, rng):
        """Row counts not divisible by 8 devices are padded and masked out."""
        for n in (7, 8, 9, 123):
            x = rng.normal(size=(n, 4))
            model = KMeans(k=2, max_iter=20, seed=0, init_mode="random").fit(x)
            # cost must equal direct recomputation on unpadded data
            d2 = ((x[:, None, :] - model.cluster_centers_[None, :, :]) ** 2).sum(-1)
            direct = d2.min(1).sum()
            assert abs(model.summary.training_cost - direct) / max(direct, 1e-9) < 1e-4


class TestChunkedScoring:
    def test_predict_and_cost_chunked_exact(self, rng, monkeypatch):
        """Row-chunked predict/compute_cost (incl. a ragged tail) match the
        unchunked results exactly."""
        x, _, _ = _blobs(rng, n=257, d=6, k=3)
        model = KMeans(k=3, max_iter=10, seed=0, init_mode="random").fit(x)
        full_pred = model.predict(x)
        full_cost = model.compute_cost(x)
        # budget of 300 elems at k=3, d=6 -> 33-row chunks (+ ragged tail)
        monkeypatch.setattr(KMeansModel, "_PREDICT_BUDGET", 300)
        np.testing.assert_array_equal(model.predict(x), full_pred)
        np.testing.assert_allclose(model.compute_cost(x), full_cost, rtol=1e-6)


class TestArgminRows:
    """``kmeans_ops.argmin_rows`` is ``jnp.argmin(axis=1)`` in every case
    the assignment sites can meet; what it is for — an f32 comparison on
    the chip — is held by tests/test_tpu_compile.py::TestAssignment."""

    @pytest.mark.parametrize("given_min", [False, True],
                             ids=["own-min", "callers-min"])
    @pytest.mark.parametrize("case", ["random", "ties", "nan", "all-inf"])
    def test_equals_jnp_argmin(self, rng, case, given_min):
        import jax
        import jax.numpy as jnp

        from oap_mllib_tpu.ops.kmeans_ops import argmin_rows

        a = rng.standard_normal((64, 37)).astype(np.float32)
        if case == "ties":
            a[:, 5] = a[:, 30] = a.min(axis=1) - 1.0  # lowest id wins
        elif case == "nan":
            a[::2, 7] = a[::2, 20] = np.nan  # first NaN wins
            a[1, :] = np.nan
        elif case == "all-inf":
            a[::3, :] = np.inf  # masked-out candidate blocks (k-means||)
            a[1, 0] = -np.inf
        a = jnp.asarray(a)
        got = jax.jit(argmin_rows)(
            a, jnp.min(a, axis=1) if given_min else None
        )
        want = jnp.argmin(a, axis=1)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(argmin_rows(a, exact=False), want)

    @pytest.mark.parametrize("tier,policy,exact", [
        ("highest", "f32", True),
        ("high", "f32", False),      # assignment matmul runs at bf16
        ("default", "f32", False),
        ("highest", "tf32", False),  # bf16_3x whatever the tier
        ("highest", "bf16", False),
    ])
    def test_only_the_strict_parity_tier_pays_for_it(self, tier, policy,
                                                     exact):
        from oap_mllib_tpu.ops import kmeans_ops

        assert kmeans_ops._exact_assign(
            kmeans_ops._assign_prec(tier), policy
        ) is exact


class TestSummaryKernel:
    """The fit records which Lloyd program the dispatch chose."""

    @pytest.mark.parametrize("cfg,want", [
        ({}, "xla"),                       # no TPU here: never pallas
        ({"kmeans_kernel": "pallas"}, "xla"),   # falls back off-TPU
        ({"model_parallel": 2}, "model_sharded"),
        ({"model_parallel": 2, "kmeans_kernel": "xla"}, "xla"),
    ], ids=["auto", "pallas-off-tpu", "model-axis", "model-axis-xla"])
    def test_in_memory_fit(self, rng, cfg, want):
        x, _, _ = _blobs(rng, n=256, d=8, k=3)
        set_config(**cfg)
        m = KMeans(k=3, max_iter=3, seed=1, init_mode="random").fit(x)
        assert m.summary.accelerated and m.summary.kernel == want
        assert m.summary.timings.root.attrs["kernel"] == want

    def test_streamed_fit(self, rng):
        from oap_mllib_tpu.data.stream import ChunkSource

        x, _, _ = _blobs(rng, n=256, d=8, k=3)
        m = KMeans(k=3, max_iter=2, seed=1, init_mode="random").fit(
            ChunkSource.from_array(x, chunk_rows=128)
        )
        assert m.summary.streamed and m.summary.kernel == "xla"


class TestModelParallel:
    """Mesh-sharded linalg for K-Means: centroids feature-sharded over the
    MODEL axis of a (data=4, model=2) mesh (survey §5 scope; the shard_map
    program in kmeans_ops.lloyd_run_model_sharded)."""

    def test_2d_mesh_matches_1d(self, rng):
        x, _, _ = _blobs(rng, n=512, d=8, k=4)
        m1 = KMeans(k=4, max_iter=25, seed=3, init_mode="random").fit(x)
        set_config(model_parallel=2)
        m2 = KMeans(k=4, max_iter=25, seed=3, init_mode="random").fit(x)
        # same host-side RNG -> same init -> identical Lloyd trajectory
        assert m1.summary.num_iter == m2.summary.num_iter
        np.testing.assert_allclose(
            m1.cluster_centers_, m2.cluster_centers_, atol=1e-5
        )
        # cost tolerance is loose: the f32 distance identity |x|^2+|c|^2-2xc
        # cancels ~4 decades on tight blobs (|x|^2 ~ 200 vs min-dist ~ 0.02),
        # and the model-sharded path sums feature-block partials in a
        # different order — centers are exact, the summed objective wobbles
        np.testing.assert_allclose(
            m1.summary.training_cost, m2.summary.training_cost, rtol=5e-3
        )
        np.testing.assert_allclose(
            m1.summary.cluster_sizes, m2.summary.cluster_sizes, atol=1e-6
        )

    def test_2d_mesh_feature_padding(self, rng):
        """d=7 does not divide model=2: zero-padded feature columns must
        not perturb centers, cost, or the returned center shape."""
        x, _, _ = _blobs(rng, n=300, d=7, k=3)
        set_config(model_parallel=2)
        model = KMeans(k=3, max_iter=30, seed=1, init_mode="random").fit(x)
        assert model.cluster_centers_.shape == (3, 7)
        ref_c, ref_cost = _oracle_lloyd(
            x, model.cluster_centers_.copy(), max_iter=1, tol=1e30
        )
        # a converged fit is a Lloyd fixed point: one more oracle step
        # cannot move the centers
        np.testing.assert_allclose(model.cluster_centers_, ref_c, atol=1e-4)
        d2 = ((x[:, None, :] - model.cluster_centers_[None, :, :]) ** 2).sum(-1)
        assert abs(model.summary.training_cost - d2.min(1).sum()) < 1e-4 * max(
            d2.min(1).sum(), 1.0
        )

    def test_2d_mesh_matches_oracle(self, rng):
        x, true_c, _ = _blobs(rng, n=640, d=8, k=4, spread=0.02)
        set_config(model_parallel=2)
        model = KMeans(k=4, max_iter=40, seed=0).fit(x)
        # well-separated blobs: recovered centers match the generators
        got = model.cluster_centers_
        for c in true_c:
            assert np.min(np.sum((got - c) ** 2, axis=1)) < 0.01

    def test_forced_xla_honored_on_model_mesh(self, rng):
        """kmeans_kernel="xla" must force the GSPMD data-parallel Lloyd
        even when model_parallel > 1 (the A/B knob), and agree with the
        model-sharded program."""
        from oap_mllib_tpu.utils import progcache

        def sharded_builds():
            # model-sharded Lloyd programs built so far (the registry
            # replaced the old functools.lru_cache here)
            return (
                progcache.stats()["by_algo"]
                .get("kmeans.lloyd_model_sharded", {})
                .get("misses", 0)
            )

        x, _, _ = _blobs(rng, n=256, d=8, k=3)
        set_config(model_parallel=2, kmeans_kernel="xla")
        before = sharded_builds()
        m1 = KMeans(k=3, max_iter=20, seed=4, init_mode="random").fit(x)
        assert sharded_builds() == before
        set_config(kmeans_kernel="auto")
        m2 = KMeans(k=3, max_iter=20, seed=4, init_mode="random").fit(x)
        np.testing.assert_allclose(
            m1.cluster_centers_, m2.cluster_centers_, atol=1e-5
        )

    def test_invalid_kernel_raises_on_model_sharded_route(self, rng):
        """kmeans_kernel validation must run even when the model axis
        routes the fit away from the pallas/xla dispatch."""
        x, _, _ = _blobs(rng, n=64, d=8, k=2)
        set_config(model_parallel=2, kmeans_kernel="typo")
        with pytest.raises(ValueError, match="kmeans_kernel"):
            KMeans(k=2, max_iter=2, init_mode="random").fit(x)

    def test_weighted_2d_mesh(self, rng):
        """Row weights thread through the model-sharded path unchanged."""
        x, _, _ = _blobs(rng, n=256, d=8, k=3)
        w = (rng.random(256) + 0.5).astype(np.float64)
        m1 = KMeans(k=3, max_iter=20, seed=5, init_mode="random").fit(
            x, sample_weight=w
        )
        set_config(model_parallel=2)
        m2 = KMeans(k=3, max_iter=20, seed=5, init_mode="random").fit(
            x, sample_weight=w
        )
        np.testing.assert_allclose(
            m1.cluster_centers_, m2.cluster_centers_, atol=1e-5
        )
        np.testing.assert_allclose(
            m1.summary.cluster_sizes, m2.summary.cluster_sizes, atol=1e-5
        )


class TestRegressions:
    def test_cosine_compute_cost_consistent_with_training(self, rng):
        """compute_cost must use the model's distance measure (cosine models
        previously got a squared-euclidean cost)."""
        x = np.abs(rng.normal(size=(60, 5))) + 0.1
        m = KMeans(k=3, distance_measure="cosine", seed=1, max_iter=30, tol=1e-6).fit(x)
        # recomputed cost on training data should match training cost closely
        tc = m.summary.training_cost
        assert abs(m.compute_cost(x) - tc) < 1e-6 + 0.05 * tc
        # and must be on the cosine scale (bounded by n since 1-cos <= 2)
        assert m.compute_cost(x) < 2 * len(x)

    def test_chunked_accumulate_matches_unchunked(self, rng):
        """row_chunks>1 (the bench kernel path) must match the unchunked
        accumulate bit-for-bit-ish on identical inputs."""
        import jax.numpy as jnp
        from oap_mllib_tpu.ops.kmeans_ops import lloyd_run

        x, _, _ = _blobs(rng, n=640, d=8, k=4)
        init = x[rng.choice(len(x), 4, replace=False)]
        xj = jnp.asarray(x, jnp.float32)
        w = jnp.ones((len(x),), jnp.float32)
        cj = jnp.asarray(init, jnp.float32)
        tol = jnp.asarray(1e-6, jnp.float32)
        c1, i1, cost1, _ = lloyd_run(xj, w, cj, 20, tol)
        c2, i2, cost2, _ = lloyd_run(xj, w, cj, 20, tol, 8)
        assert int(i1) == int(i2)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-4, rtol=1e-5)
        # f32 cost sums reassociate across chunk boundaries -> ~1e-4 rel drift
        np.testing.assert_allclose(float(cost1), float(cost2), rtol=1e-3)

    def test_chunked_pads_indivisible_rows(self, rng):
        """Rows that don't divide row_chunks pad with weight-0 rows inside
        lloyd_run (they used to raise) — the budget stays enforceable for
        ANY n and results match the unchunked loop."""
        import jax.numpy as jnp
        from oap_mllib_tpu.ops.kmeans_ops import lloyd_run

        x, _, _ = _blobs(rng, n=101, d=5, k=3)
        init = x[rng.choice(len(x), 3, replace=False)]
        xj = jnp.asarray(x, jnp.float32)
        w = jnp.ones((len(x),), jnp.float32)
        cj = jnp.asarray(init, jnp.float32)
        tol = jnp.asarray(1e-6, jnp.float32)
        c1, i1, cost1, n1 = lloyd_run(xj, w, cj, 15, tol)
        c2, i2, cost2, n2 = lloyd_run(xj, w, cj, 15, tol, 4)  # 101 % 4 != 0
        assert int(i1) == int(i2)
        np.testing.assert_allclose(
            np.asarray(c1), np.asarray(c2), atol=1e-5, rtol=1e-5
        )
        np.testing.assert_allclose(float(cost1), float(cost2), rtol=1e-3)
        np.testing.assert_allclose(
            np.asarray(n1), np.asarray(n2), atol=1e-5
        )

    def test_auto_row_chunks_budget_holds_for_odd_n(self):
        """Regression (ISSUE 2 satellite): an odd / non-power-of-two-
        divisible n used to silently return 1 chunk, letting the (n, k)
        distance buffer blow past the element budget.  The budget is a
        hard bound now."""
        from oap_mllib_tpu.ops.kmeans_ops import auto_row_chunks

        budget = 4096
        for n in (1001, 999_999, 2**15 + 1):
            chunks = auto_row_chunks(n, 64, budget_elems=budget)
            assert chunks > 1
            assert (-(-n // chunks)) * 64 <= budget, (n, chunks)
        # small fits still take the no-scan-overhead single chunk
        assert auto_row_chunks(1000, 4) == 1

    @pytest.mark.parametrize("target", [1, 7, 64, 256, 512, 1024])
    def test_slot_chunk_size_matches_brute_force(self, target):
        """The O(sqrt cap) paired-divisor enumeration must agree with
        the exhaustive scan: the largest divisor of cap in [target // 4,
        target], else the largest one <= 2 * target."""
        from oap_mllib_tpu.ops.kmeans_ops import _slot_chunk_size

        for cap in list(range(1, 700, 13)) + [
            1024, 1536, 2048, 2084, 3200, 4000, 4100, 4124,
        ]:
            divisors = [c for c in range(1, cap + 1) if cap % c == 0]
            fine = [c for c in divisors if target // 4 <= c <= target]
            brute = max(fine or [c for c in divisors if c <= 2 * target])
            assert _slot_chunk_size(cap, target) == brute, (cap, target)

    @pytest.mark.parametrize("cap, chunk", [
        (4000, 500),  # the cells' k = 1000: eight chunks, 512 MXU columns
        (8000, 500), (4096, 512), (640, 320), (3200, 400),
        (16, 16), (400, 400), (512, 512),  # a small k keeps one chunk
        (4 * 131, 262), (4 * 263, 263),  # a prime k: 2k, or k past 256
        # a prime k past the target has no divisor in [128, 512]: not the
        # 4 a plain "largest <= 512" gives (521 sheets a round), but k,
        # the largest <= 1024
        (4 * 521, 521), (4 * 1021, 1021),
        (4 * 1031, 4),  # a prime k past 1024 has only 1, 2, 4 below it
    ])
    def test_slot_chunk_size_of_a_rounds_capacity(self, cap, chunk):
        """The rule the rounds use (the default target), at cap = 4k."""
        from oap_mllib_tpu.ops.kmeans_ops import _slot_chunk_size

        assert _slot_chunk_size(cap) == chunk
        assert cap % chunk == 0

    def test_bad_precision_string_raises(self, rng):
        import jax.numpy as jnp
        from oap_mllib_tpu.ops.kmeans_ops import lloyd_run

        x = jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)
        w = jnp.ones((8,), jnp.float32)
        with pytest.raises(ValueError):
            lloyd_run(x, w, x[:2], 2, jnp.asarray(0.0, jnp.float32), 1, "Highest")

    def test_cluster_sizes_in_summary(self, rng):
        x, _, assign = _blobs(rng, n=400, k=4)
        m = KMeans(k=4, max_iter=30, tol=1e-6, seed=7).fit(x)
        sizes = m.summary.cluster_sizes
        assert sizes is not None and sizes.shape == (4,)
        assert int(sizes.sum()) == 400
        # blob sizes recovered (order-insensitive)
        np.testing.assert_array_equal(
            np.sort(sizes.astype(int)), np.sort(np.bincount(assign)))

    def test_pmml_export(self, tmp_path, rng):
        import xml.etree.ElementTree as ET

        x, _, _ = _blobs(rng, k=3)
        m = KMeans(k=3, seed=1).fit(x)
        p = str(tmp_path / "model.pmml")
        m.to_pmml(p)
        tree = ET.parse(p)
        ns = {"p": "http://www.dmg.org/PMML-4_3"}
        cm = tree.getroot().find("p:ClusteringModel", ns)
        assert cm is not None and cm.get("numberOfClusters") == "3"
        clusters = cm.findall("p:Cluster", ns)
        assert len(clusters) == 3
        arr = clusters[0].find("p:Array", ns)
        vals = [float(v) for v in arr.text.split()]
        np.testing.assert_allclose(vals, m.cluster_centers_[0])
