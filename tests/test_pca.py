"""PCA parity + behavior tests.

Modeled on the reference's IntelPCASuite (IntelPCASuite.scala:39-104):
oracle = independent covariance eigendecomposition, absTol 1e-5-ish,
principal components compared BY ABSOLUTE VALUE (eigenvector sign flip,
:80-82), only where explained variance is non-negligible (:84), plus
read/write round-trip (:90-104).
"""

import numpy as np
import pytest

from oap_mllib_tpu import PCA, PCAModel
from oap_mllib_tpu.config import set_config


def _data(rng, n=500, d=12):
    """Correlated gaussian data with a clear spectrum."""
    basis = rng.normal(size=(d, d))
    scales = np.linspace(3.0, 0.1, d)
    return rng.normal(size=(n, d)) @ (basis * scales[None, :])


def _oracle(x, k):
    """Independent oracle: covariance eigh (Spark RowMatrix semantics)."""
    xc = x - x.mean(0)
    cov = xc.T @ xc / (len(x) - 1)
    vals, vecs = np.linalg.eigh(cov)
    vals, vecs = vals[::-1], vecs[:, ::-1]
    return vecs[:, :k], vals[:k] / vals.sum()


class TestParity:
    def test_components_match_oracle_sign_insensitive(self, rng):
        x = _data(rng)
        k = 5
        model = PCA(k=k).fit(x)
        assert model.summary["accelerated"]
        pc_ref, ev_ref = _oracle(x, k)
        # sign-insensitive compare where explained variance is significant
        # (reference IntelPCASuite.scala:80-86)
        for j in range(k):
            if ev_ref[j] > 1e-5:
                np.testing.assert_allclose(
                    np.abs(model.components_[:, j]), np.abs(pc_ref[:, j]),
                    atol=1e-3,
                )
        np.testing.assert_allclose(model.explained_variance_, ev_ref, atol=1e-4)

    def test_accelerated_vs_fallback(self, rng):
        x = _data(rng)
        m_acc = PCA(k=4).fit(x)
        set_config(device="cpu")
        m_fb = PCA(k=4).fit(x)
        assert not m_fb.summary["accelerated"]
        np.testing.assert_allclose(
            np.abs(m_acc.components_), np.abs(m_fb.components_), atol=1e-3
        )
        np.testing.assert_allclose(
            m_acc.explained_variance_, m_fb.explained_variance_, atol=1e-4
        )

    def test_explained_variance_sums_below_one(self, rng):
        x = _data(rng)
        model = PCA(k=3).fit(x)
        assert 0 < model.explained_variance_.sum() <= 1.0 + 1e-6
        # descending
        assert np.all(np.diff(model.explained_variance_) <= 1e-9)


class TestPrecisionTiers:
    """Tier threading through the estimator (every tier runs the centered
    two-pass Gram; on CPU all tiers are full f32, so these check the
    plumbing + oracle parity; the per-tier bf16 error bounds are pinned
    on tests_tpu)."""

    def test_high_tier_matches_highest(self, rng):
        x = _data(rng, n=400, d=12) + 25.0  # large means: worst case
        m_hi = PCA(k=4).fit(x)
        set_config(matmul_precision="high")
        m_fast = PCA(k=4).fit(x)
        np.testing.assert_allclose(
            m_fast.explained_variance_, m_hi.explained_variance_, atol=1e-5
        )
        np.testing.assert_allclose(
            np.abs(m_fast.components_), np.abs(m_hi.components_), atol=1e-4
        )

    def test_high_tier_model_sharded(self, rng):
        x = _data(rng, n=256, d=8) + 10.0
        set_config(matmul_precision="high", model_parallel=2)
        m = PCA(k=3).fit(x)
        assert m.summary["mesh_shape"]["model"] == 2
        pc_ref, ev_ref = _oracle(x, 3)
        np.testing.assert_allclose(m.explained_variance_, ev_ref, atol=1e-4)
        np.testing.assert_allclose(
            np.abs(m.components_), np.abs(pc_ref), atol=1e-3
        )

    def test_invalid_tier_raises(self, rng):
        x = _data(rng, n=64, d=6)
        set_config(matmul_precision="typo")
        with pytest.raises(ValueError, match="matmul_precision"):
            PCA(k=2).fit(x)

    def test_large_mean_cancellation_regression(self, rng):
        """mean >> stddev data at f32: the retired raw-moment form lost
        ~4e-3 relative through the gram ~ n*mu*mu^T cancellation; the
        centered form must stay on the oracle."""
        x = rng.normal(size=(2000, 8)) + 100.0
        model = PCA(k=3).fit(x.astype(np.float32))
        pc_ref, ev_ref = _oracle(x, 3)
        np.testing.assert_allclose(model.explained_variance_, ev_ref, atol=1e-4)
        np.testing.assert_allclose(
            np.abs(model.components_), np.abs(pc_ref), atol=1e-3
        )


class TestModelParallel:
    """Mesh-sharded linalg: the Gram/covariance rows sharded over the
    MODEL axis of a 2-D (data=4, model=2) mesh (survey §5's "mesh-sharded
    linalg" scope — a real estimator path, not just the driver dryrun)."""

    def test_2d_mesh_matches_oracle(self, rng):
        x = _data(rng, n=400, d=12)
        k = 5
        set_config(model_parallel=2)
        model = PCA(k=k).fit(x)
        assert model.summary["accelerated"]
        # the fit really ran on a (4, 2) mesh
        assert model.summary["mesh_shape"] == {"data": 4, "model": 2}
        pc_ref, ev_ref = _oracle(x, k)
        for j in range(k):
            if ev_ref[j] > 1e-5:
                np.testing.assert_allclose(
                    np.abs(model.components_[:, j]), np.abs(pc_ref[:, j]),
                    atol=1e-3,
                )
        np.testing.assert_allclose(model.explained_variance_, ev_ref, atol=1e-4)

    def test_2d_mesh_feature_padding(self, rng):
        """d=11 does not divide model=2: zero-padded feature columns must
        not perturb the components or the variance ratios."""
        x = _data(rng, n=300, d=11)
        set_config(model_parallel=2)
        model = PCA(k=3).fit(x)
        assert model.components_.shape == (11, 3)
        pc_ref, ev_ref = _oracle(x, 3)
        np.testing.assert_allclose(
            np.abs(model.components_), np.abs(pc_ref), atol=1e-3
        )
        np.testing.assert_allclose(model.explained_variance_, ev_ref, atol=1e-4)

    def test_2d_mesh_rank_deficient_padding_tie(self, rng):
        """Rank-deficient data + padded columns: the genuine null-space
        eigenvector must win the tie at eigenvalue 0, never a padded basis
        vector (which would slice to a zero component column)."""
        # d=3 padded to 4 under model=2; data spans only 2 directions
        base = rng.normal(size=(200, 2))
        x = np.concatenate([base, (base[:, :1] + base[:, 1:])], axis=1)  # col3 = col1+col2
        set_config(model_parallel=2)
        model = PCA(k=3).fit(x)
        norms = np.linalg.norm(model.components_, axis=0)
        np.testing.assert_allclose(norms, 1.0, atol=1e-4)  # no zero column
        # the k=3 component is the true null direction (1,1,-1)/sqrt(3)
        np.testing.assert_allclose(
            np.abs(model.components_[:, 2]), np.abs(np.array([1, 1, -1]) / np.sqrt(3)),
            atol=1e-3,
        )

    def test_2d_matches_1d(self, rng):
        x = _data(rng, n=256, d=8)
        m1 = PCA(k=4).fit(x)
        set_config(model_parallel=2)
        m2 = PCA(k=4).fit(x)
        assert m2.summary["mesh_shape"]["model"] == 2
        assert m1.summary["mesh_shape"]["model"] == 1
        np.testing.assert_allclose(
            np.abs(m1.components_), np.abs(m2.components_), atol=1e-4
        )


class TestSummaryKernel:
    """The fit records which Gram program the dispatch chose."""

    @pytest.mark.parametrize("cfg,want", [
        ({}, "xla"),                          # no TPU here: never pallas
        ({"pca_kernel": "pallas"}, "xla"),    # falls back off-TPU
        ({"model_parallel": 2}, "model_sharded"),
    ], ids=["auto", "pallas-off-tpu", "model-axis"])
    def test_in_memory_fit(self, rng, cfg, want):
        set_config(**cfg)
        m = PCA(k=3).fit(_data(rng, n=200, d=8))
        assert m.summary["accelerated"] and m.summary["kernel"] == want

    def test_streamed_fit(self, rng):
        from oap_mllib_tpu.data.stream import ChunkSource

        m = PCA(k=3).fit(
            ChunkSource.from_array(_data(rng, n=200, d=8), chunk_rows=64)
        )
        assert m.summary["streamed"] and m.summary["kernel"] == "xla"


class TestBehavior:
    def test_shapes(self, rng):
        x = _data(rng, n=100, d=7)
        model = PCA(k=3).fit(x)
        assert model.components_.shape == (7, 3)
        assert model.explained_variance_.shape == (3,)
        assert model.transform(x).shape == (100, 3)

    def test_transform_no_centering_spark_parity(self, rng):
        """Spark's PCAModel.transform projects WITHOUT subtracting the mean."""
        x = _data(rng, n=50, d=5) + 10.0  # big offset
        model = PCA(k=2).fit(x)
        expected = x.astype(np.float32) @ model.components_
        np.testing.assert_allclose(model.transform(x), expected, atol=1e-3)

    def test_k_validation(self, rng):
        with pytest.raises(ValueError):
            PCA(k=0)
        with pytest.raises(ValueError):
            PCA(k=10).fit(np.zeros((5, 3)))

    def test_uneven_rows(self, rng):
        for n in (9, 17, 101):
            x = _data(rng, n=n, d=6)
            model = PCA(k=2).fit(x)
            pc_ref, ev_ref = _oracle(x, 2)
            np.testing.assert_allclose(
                np.abs(model.components_), np.abs(pc_ref), atol=1e-3
            )


class TestRandomizedSolver:
    """pca_solver="randomized": top-k subspace iteration vs full eigh.
    Vector parity is claimed ONLY on decaying spectra (the ops docstring
    contract); near-flat spectra pin eigenvalue agreement alone."""

    def _decaying(self, rng, n=2000, d=64):
        # strongly decaying spectrum: well-separated top eigenpairs
        scales = 2.0 ** -np.arange(d)
        basis = np.linalg.qr(rng.normal(size=(d, d)))[0]
        x = rng.normal(size=(n, d)) * scales[None, :] * 10
        return (x @ basis.T).astype(np.float32)

    def test_matches_eigh_on_decaying_spectrum(self, rng):
        from oap_mllib_tpu.config import set_config

        x = self._decaying(rng)
        m_eigh = PCA(k=5).fit(x)
        set_config(pca_solver="randomized")
        m_rand = PCA(k=5).fit(x)
        np.testing.assert_allclose(
            m_rand.explained_variance_, m_eigh.explained_variance_,
            rtol=1e-4, atol=1e-6,
        )
        # sign-insensitive vector match (IntelPCASuite pattern)
        dots = np.abs(
            np.einsum("dk,dk->k", m_rand.components_, m_eigh.components_)
        )
        assert np.all(dots > 1.0 - 1e-4), dots

    def test_flat_spectrum_eigenvalues_only(self, rng):
        """Isotropic noise: the top-k subspace is ill-defined, so only
        the eigenVALUES are pinned (to the flat level)."""
        from oap_mllib_tpu.config import set_config

        x = rng.normal(size=(5000, 32)).astype(np.float32)
        m_eigh = PCA(k=4).fit(x)
        set_config(pca_solver="randomized")
        m_rand = PCA(k=4).fit(x)
        np.testing.assert_allclose(
            m_rand.explained_variance_, m_eigh.explained_variance_,
            rtol=0.05,
        )

    def test_streamed_randomized(self, rng):
        from oap_mllib_tpu.config import set_config
        from oap_mllib_tpu.data.stream import ChunkSource

        x = self._decaying(rng, n=1500, d=32)
        set_config(pca_solver="randomized")
        m_s = PCA(k=3).fit(ChunkSource.from_array(x, chunk_rows=256))
        m_m = PCA(k=3).fit(x)
        np.testing.assert_allclose(
            np.abs(m_s.components_), np.abs(m_m.components_), atol=1e-4
        )

    def test_model_sharded_randomized(self, rng):
        """model_parallel=2 pads feature dims; the randomized path must
        slice the padding off (NOT -1-demote it: subspace iteration
        ranks by |eigenvalue|)."""
        from oap_mllib_tpu.config import set_config

        x = self._decaying(rng, n=1000, d=31)  # 31 % 2 != 0 -> padded
        m_ref = PCA(k=3).fit(x)
        set_config(pca_solver="randomized", model_parallel=2)
        m = PCA(k=3).fit(x)
        assert m.components_.shape == (31, 3)
        dots = np.abs(np.einsum("dk,dk->k", m.components_, m_ref.components_))
        assert np.all(dots > 1.0 - 1e-3), dots

    def test_k_larger_than_probe_cap(self, rng):
        """k + oversample > d clamps the probe to d and still works."""
        from oap_mllib_tpu.config import set_config

        x = self._decaying(rng, n=500, d=10)
        set_config(pca_solver="randomized")
        m = PCA(k=9).fit(x)
        assert m.components_.shape == (10, 9)
        assert np.isfinite(m.components_).all()

    def test_invalid_solver_raises(self, rng):
        from oap_mllib_tpu.config import set_config

        set_config(pca_solver="randomised")
        with pytest.raises(ValueError, match="pca_solver"):
            PCA(k=2).fit(_data(rng, n=50, d=5))

    def test_tuning_knobs_flow_through(self, rng):
        """pca_rand_oversample/iters reach the solver: cranking them on a
        weakly-gapped spectrum tightens the eigenvalues toward eigh."""
        from oap_mllib_tpu.config import set_config

        x = rng.normal(size=(3000, 48)).astype(np.float32)
        ref = PCA(k=4).fit(x).explained_variance_
        set_config(pca_solver="randomized", pca_rand_oversample=2,
                   pca_rand_iters=1)
        loose = PCA(k=4).fit(x).explained_variance_
        set_config(pca_rand_oversample=44, pca_rand_iters=24)
        tight = PCA(k=4).fit(x).explained_variance_
        assert np.abs(tight - ref).max() < np.abs(loose - ref).max()
        np.testing.assert_allclose(tight, ref, rtol=5e-3)
        set_config(pca_rand_iters=0)
        with pytest.raises(ValueError, match="pca_rand"):
            PCA(k=4).fit(x)


class TestPersistence:
    def test_save_load_roundtrip(self, tmp_path, rng):
        x = _data(rng)
        model = PCA(k=3).fit(x)
        p = str(tmp_path / "pca_model")
        model.save(p)
        loaded = PCAModel.load(p)
        np.testing.assert_array_equal(loaded.components_, model.components_)
        np.testing.assert_array_equal(
            loaded.explained_variance_, model.explained_variance_
        )


def _bench_harness():
    """``benchmarks/run.py`` as a module (under a name of its own: the
    suite has no other ``run``), which finds the cell's files by name."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "run.py",
    )
    spec = importlib.util.spec_from_file_location("oap_bench_run", path)
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    return harness


class TestCovariancePhase:
    """The ``covariance`` phase ends on a READY covariance and says what
    ran (ISSUE 31): without the wait the Gram's device time is booked to
    ``eigh``."""

    @pytest.mark.parametrize("cfg,want,rows", [
        ({}, "xla", 8 * 256), ({"model_parallel": 2}, "model_sharded", 4 * 256),
    ], ids=["one-axis", "model-axis"])
    def test_attrs_say_what_ran(self, rng, cfg, want, rows):
        set_config(**cfg)
        m = PCA(k=3).fit(_data(rng, n=200, d=8).astype(np.float32))
        cov = m.summary["timings"].root.node("covariance")
        assert cov.attrs["kernel"] == want == m.summary["kernel"]
        # the rows the program walks: the table's bucket (256 a device of
        # the data axis), pad included
        assert cov.attrs["rows"] == rows
        # the Gram's bf16 passes are the Pallas kernel's to state
        assert "mxu_passes" not in cov.attrs

    def test_pallas_route_states_its_mxu_passes(self, rng, monkeypatch):
        from oap_mllib_tpu.models import pca as pca_mod
        from oap_mllib_tpu.ops.pallas import pca_kernel

        assert pca_kernel.MXU_PASSES["highest"] == {"gram": 6}
        assert set(pca_kernel.MXU_PASSES) == {"highest", "high", "default"}
        # the dispatch picks Pallas on a TPU alone: name it, run the XLA pass
        monkeypatch.setattr(pca_mod, "_gram_kernel", lambda *a: "pallas")
        set_config(matmul_precision="high")
        m = PCA(k=3).fit(_data(rng, n=200, d=8).astype(np.float32))
        cov = m.summary["timings"].root.node("covariance")
        assert cov.attrs["kernel"] == "pallas"
        assert cov.attrs["mxu_passes"] == {"gram": 3}

    def test_phase_waits_for_its_covariance(self, rng, monkeypatch):
        import jax

        from oap_mllib_tpu.telemetry import spans

        waits = []
        real = jax.block_until_ready

        def spy(v):
            span = spans.current_span()
            waits.append((
                span.path if span is not None else None,
                [np.shape(a) for a in jax.tree_util.tree_leaves(v)],
            ))
            return real(v)

        monkeypatch.setattr(jax, "block_until_ready", spy)
        PCA(k=3).fit(_data(rng, n=200, d=8).astype(np.float32))
        # inside the phase's fetch leaf (the host is blocked on results)
        assert [w for w in waits if w[0].startswith("covariance")] == [
            ("covariance/fetch", [(8, 8)])
        ]
        # and the staging phase's wait for table and mask — every one of
        # the 8 devices' shards of each — in the upload's land leaf
        assert (
            "table_convert/upload/land", [(256, 8)] * 8 + [(256,)] * 8
        ) in waits


class TestBenchmarkCellAtRehearseSize:
    """``pca_d512_k10`` at its ``rehearse`` size, on ONE device and
    through three pieces: the benchmark's plain reference judges the fit
    under the configuration's limits, as it judges every fit of a window
    on the chip."""

    @pytest.mark.parametrize("seed", [3, 2_147_483_659])
    def test_fit_through_three_pieces_is_correct(self, monkeypatch, seed):
        from oap_mllib_tpu.data import table as table_mod
        from oap_mllib_tpu.models import pca as pca_mod
        from oap_mllib_tpu.parallel.mesh import get_mesh

        harness = _bench_harness()
        _, cell, cfg, _ = harness.load_cell(
            "pca_d512_k10.fit_loop", rehearse=True
        )
        adapter = harness._module("estimators", cfg["estimator"])
        ref = harness._module("reference", adapter.REFERENCE)
        x = adapter.make_data(cfg, cfg["rows_per_chip"] * cell["chips"], seed)
        mesh = get_mesh(n_devices=1)
        monkeypatch.setattr(pca_mod, "get_mesh", lambda: mesh)
        # three pieces, two in flight on the one device
        monkeypatch.setattr(
            table_mod, "_UPLOAD_PIECE_BYTES",
            2 * -(-x.shape[0] // 3) * x.shape[1] * x.itemsize,
        )
        monkeypatch.setattr(table_mod, "_ONE_DEVICE_PIECES_IN_FLIGHT", 2)
        set_config(matmul_precision=cfg["matmul_precision"],
                   pca_solver=cfg["pca_solver"])
        result, info = adapter.fit(cfg, x, seed)
        m_up = PCA(k=cfg["k"]).fit(x).summary["timings"].root.node(
            "table_convert/upload"
        )
        assert m_up.attrs["pieces"] == 3 and m_up.attrs["shards"] == 1
        assert info["accelerated"] and not any(info["resilience"].get(k) for k in
                                               ("degradations", "retries", "faults"))
        plain = ref.fit_plain(x, cfg, seed)
        limits = cfg["limits"]
        for name, answer in (("program", result), ("plain", plain)):
            numbers = ref.judge(x, cfg, [answer], seed)
            assert set(numbers) == set(limits)
            for n, v in numbers.items():
                assert v <= limits[n], (name, n, v, limits[n])
        np.testing.assert_allclose(
            result["ratios"], plain["ratios"], rtol=2 * limits["ratio_gap"]
        )

    @pytest.mark.parametrize("pieces,breach", [(3, False), (1, True)])
    def test_adapter_holds_the_upload_to_the_configuration(
            self, monkeypatch, capsys, pieces, breach):
        """``estimators/pca_staged.py`` ends the run (exit code 4, a line
        on stderr) at a fit whose table went up in larger pieces than the
        configuration's ``expect_upload`` allows, and lets every other
        fit through."""
        from oap_mllib_tpu.data import table as table_mod
        from oap_mllib_tpu.models import pca as pca_mod
        from oap_mllib_tpu.parallel.mesh import get_mesh

        harness = _bench_harness()
        _, cell, cfg, _ = harness.load_cell(
            "pca_d512_k10.fit_loop", rehearse=True
        )
        adapter = harness._module("estimators", cfg["estimator"])
        x = adapter.make_data(cfg, cfg["rows_per_chip"] * cell["chips"], 5)
        mesh = get_mesh(n_devices=1)
        monkeypatch.setattr(pca_mod, "get_mesh", lambda: mesh)
        third = -(-x.shape[0] // 3) * x.shape[1] * x.itemsize
        # the allowance of a third of the table (and of its mask); the
        # program's piece at that or, for the breach, over the table
        cfg = dict(cfg, expect_upload={"piece_bytes_max": third + x.shape[0] * 4})
        monkeypatch.setattr(
            table_mod, "_UPLOAD_PIECE_BYTES", third if pieces == 3 else x.nbytes
        )
        monkeypatch.setattr(table_mod, "_ONE_DEVICE_PIECES_IN_FLIGHT", 1)
        if not breach:
            result, info = adapter.fit(cfg, x, 5)
            assert result["components"].shape == (cfg["d"], cfg["k"])
            assert "table_convert/upload" in info["phases"]
            return
        with pytest.raises(SystemExit) as stop:
            adapter.fit(cfg, x, 5)
        assert stop.value.code == adapter.EXIT_CANNOT_STAGE == 4
        said = capsys.readouterr().err
        assert "cannot run pca_d512_k10" in said and "1 piece(s)" in said
