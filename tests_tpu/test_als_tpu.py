"""Compiled-mode TPU tests for ALS: grouped-edge vs COO parity on hardware.

tests/test_als.py proves both program families against the NumPy oracle on
the CPU pseudo-cluster; this suite compiles them for the real chip and
holds them to each other — the grouped-edge path's batched (r+1, r+2) MXU
matmuls and the COO path's segment-sum scatters take different XLA-TPU
lowering routes, so a precision or Mosaic regression in either shows up
here first.  Both feedback modes are covered (the reference accelerates
implicit only, ALS.scala:925; we accelerate both).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from oap_mllib_tpu.ops import als_ops


def _synthetic(rng, n_users=512, n_items=256, nnz=8192):
    u = rng.integers(0, n_users, size=nnz).astype(np.int32)
    i = rng.integers(0, n_items, size=nnz).astype(np.int32)
    r = (rng.random(nnz) * 4 + 1).astype(np.float32)
    return u, i, r


class TestGroupedVsCooCompiled:
    @pytest.mark.parametrize("implicit", [True, False])
    def test_full_loop_parity(self, rng, implicit):
        n_users, n_items, rank, iters = 512, 256, 8, 3
        u, i, r = _synthetic(rng, n_users, n_items)
        x0 = (rng.normal(size=(n_users, rank)) * 0.1).astype(np.float32)
        y0 = (rng.normal(size=(n_items, rank)) * 0.1).astype(np.float32)
        valid = jnp.ones((len(u),), jnp.float32)
        reg, alpha = 0.1, 10.0

        by_user = als_ops.build_grouped_edges(u, i, r, n_users)
        by_item = als_ops.build_grouped_edges(i, u, r, n_items)
        xg, yg = als_ops.als_run_grouped(
            *[jnp.asarray(a) for a in by_user],
            *[jnp.asarray(a) for a in by_item],
            jnp.asarray(x0), jnp.asarray(y0),
            n_users, n_items, iters, reg, alpha, implicit,
        )
        if implicit:
            xc, yc = als_ops.als_implicit_run(
                jnp.asarray(u), jnp.asarray(i), jnp.asarray(r), valid,
                jnp.asarray(x0), jnp.asarray(y0),
                n_users, n_items, iters, reg, alpha,
            )
        else:
            xc, yc = als_ops.als_explicit_run(
                jnp.asarray(u), jnp.asarray(i), jnp.asarray(r), valid,
                jnp.asarray(x0), jnp.asarray(y0),
                n_users, n_items, iters, reg,
            )
        np.testing.assert_allclose(np.asarray(xg), np.asarray(xc), atol=2e-3)
        np.testing.assert_allclose(np.asarray(yg), np.asarray(yc), atol=2e-3)

    def test_partials_parity(self, rng):
        """One half-iteration's (A, b, n_reg) partials: grouped == COO."""
        n_users, n_items, rank = 300, 200, 10
        u, i, r = _synthetic(rng, n_users, n_items, nnz=4096)
        y = rng.normal(size=(n_items, rank)).astype(np.float32)
        valid = jnp.ones((len(u),), jnp.float32)
        a1, b1, n1 = als_ops.normal_eq_partials(
            jnp.asarray(u), jnp.asarray(i), jnp.asarray(r), valid,
            jnp.asarray(y), n_users, 40.0, True,
        )
        src_g, conf_g, valid_g, group_dst = als_ops.build_grouped_edges(
            u, i, r, n_users
        )
        a2, b2, n2 = als_ops.normal_eq_partials_grouped(
            jnp.asarray(src_g), jnp.asarray(conf_g), jnp.asarray(valid_g),
            jnp.asarray(group_dst), jnp.asarray(y), n_users, 40.0, True,
        )
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=2e-4,
                                   atol=2e-2)
        np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), rtol=2e-4,
                                   atol=2e-2)
        np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), atol=1e-3)


class TestEstimatorCompiled:
    @pytest.mark.parametrize("implicit", [True, False])
    def test_fit_improves_rmse(self, rng, implicit):
        """ALS().fit end-to-end on the session backend: reconstruction
        improves over the init and the accelerated path was taken."""
        from oap_mllib_tpu.models.als import ALS

        n_users, n_items = 400, 300
        # planted low-rank structure so ALS has signal to recover
        xt = rng.normal(size=(n_users, 6)).astype(np.float32)
        yt = rng.normal(size=(n_items, 6)).astype(np.float32)
        u, i, _ = _synthetic(rng, n_users, n_items, nnz=6000)
        r = np.abs(np.sum(xt[u] * yt[i], axis=1)) + 0.1
        m = ALS(rank=6, max_iter=8, reg_param=0.05, alpha=40.0,
                implicit_prefs=implicit, seed=7).fit(u, i, r)
        assert m.summary["accelerated"]
        pred = m.predict(u, i)
        if implicit:
            # implicit predicts preference: observed pairs must score well
            # above random pairs (the model's actual ranking semantics —
            # absolute closeness to 1 depends on reg/alpha shrinkage)
            ru = rng.integers(0, n_users, size=len(u)).astype(np.int32)
            ri = rng.integers(0, n_items, size=len(u)).astype(np.int32)
            rand_pred = m.predict(ru, ri)
            assert float(pred.mean()) > float(rand_pred.mean()) + 0.2
        else:
            rmse = float(np.sqrt(np.mean((pred - r) ** 2)))
            assert rmse < 0.5 * float(np.std(r))

    def test_recommend_scores_match_predict_on_hardware(self, rng):
        """The recommend matmul must run at HIGHEST precision: TPU's
        default bf16 matmul drifts the returned scores ~1e-3 off
        predict() and can swap near-tie rankings — invisible to the CPU
        suite (f32 matmuls there), caught only on hardware (round 5)."""
        from oap_mllib_tpu.models.als import ALS

        u, i, _ = _synthetic(rng, 80, 60, nnz=2500)
        r = (rng.random(len(u)) * 4 + 1).astype(np.float32)
        m = ALS(rank=4, max_iter=2, implicit_prefs=True, seed=1).fit(u, i, r)
        ids, scores = m.recommend_for_all_users(5, with_scores=True)
        uu = np.repeat(np.arange(ids.shape[0]), 5)
        np.testing.assert_allclose(
            scores.ravel(), m.predict(uu, ids.ravel()), atol=1e-5
        )
        sub = np.array([7, 3, 7])
        sids, sscores = m.recommend_for_users(sub, 5, with_scores=True)
        full = m.user_factors_[sub] @ m.item_factors_.T
        np.testing.assert_allclose(
            np.take_along_axis(full, sids, axis=1), sscores, atol=1e-5
        )


class TestGroupedChunkedCompiled:
    def test_chunked_scan_path_compiled(self, rng, monkeypatch):
        """The G-blocked lax.scan partials (the ML-25M-on-one-chip path)
        compile for the real chip and match the unchunked program — the
        flat (n_dst, (r+1)(r+2)) carry and the padded dummy groups take
        lowering routes the interpret-mode CPU test cannot validate."""
        n_users, n_items, rank, iters = 512, 256, 8, 2
        u, i, r = _synthetic(rng, n_users, n_items)
        x0 = (rng.normal(size=(n_users, rank)) * 0.1).astype(np.float32)
        y0 = (rng.normal(size=(n_items, rank)) * 0.1).astype(np.float32)
        by_user = als_ops.build_grouped_edges(u, i, r, n_users)
        by_item = als_ops.build_grouped_edges(i, u, r, n_items)
        dev = [jnp.asarray(a) for a in (*by_user, *by_item)]

        def run():
            return als_ops.als_run_grouped(
                *dev, jnp.asarray(x0), jnp.asarray(y0),
                n_users, n_items, iters, 0.1, 10.0, True,
            )

        x1, y1 = run()
        # force the scan path: budget far below this side's (G, P, r) size
        # (odd split so the dummy-group padding lowers on hardware too)
        monkeypatch.setattr(als_ops, "_GROUPED_BUDGET_ELEMS", 1 << 14)
        assert als_ops._grouped_block_count(*by_user[0].shape, rank) > 1
        als_ops._als_run_grouped_jit.clear_cache()
        x2, y2 = run()
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), atol=2e-4)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
        # monkeypatch teardown restores the budget; clearing the jit cache
        # keeps the small-budget trace from leaking into later tests
        als_ops._als_run_grouped_jit.clear_cache()


class TestGatherWalkCompiled:
    """The factor-row gather as the Pallas walk over the packed,
    VMEM-resident table (ops/pallas/als_gather.py) against XLA's gather,
    bit for bit, at the ALS cell's shapes: one block of 2^20 slots in
    groups of 128 from each side's table."""

    @pytest.mark.parametrize("n_src", [624961, 500495])
    def test_a_block_at_the_cells_shape(self, rng, n_src):
        import jax

        from oap_mllib_tpu.ops.pallas import als_gather

        r, groups, p = 10, 8192, 128
        f = jnp.asarray(rng.normal(size=(n_src, r)).astype(np.float32))
        src = rng.integers(0, n_src, (groups, p)).astype(np.int32)
        src[:, -5:] = 0  # pad slots
        src[0, 0] = n_src - 1
        src = jnp.asarray(src)
        conf = jnp.asarray((rng.integers(0, 11, (groups, p)) * 10).astype(np.float32))
        valid = jnp.asarray((np.arange(p) < p - 5).astype(np.float32)[None].repeat(groups, 0))
        want = jax.jit(lambda f, s: f.T[:, s])(f, src)
        got = jax.jit(lambda f, s: als_ops.gather_factor_rows(f, s, "pallas"))(f, src)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
        moments = [
            jax.jit(lambda s, c, v, f, g=g: als_ops.grouped_block_moments(
                s, c, v, f, 40.0, True, "f32", g))(src, conf, valid, f)
            for g in ("xla", "pallas")
        ]
        assert np.asarray(moments[0]).tobytes() == np.asarray(moments[1]).tobytes()
        assert als_ops.resolve_gather_kernel(n_src, r, np.float32) == "pallas"
        assert als_gather.table_bytes(n_src, r) <= als_gather.TABLE_BOUND_BYTES

    def test_a_grouped_fit_through_either_gather(self, rng):
        n_users, n_items, rank, iters = 3000, 2000, 10, 3
        u, i, r = _synthetic(rng, n_users, n_items, nnz=60000)
        x0 = jnp.asarray((rng.normal(size=(n_users, rank)) * 0.1).astype(np.float32))
        y0 = jnp.asarray((rng.normal(size=(n_items, rank)) * 0.1).astype(np.float32))
        dev = [jnp.asarray(a) for a in (*als_ops.build_grouped_edges(u, i, r, n_users),
                                         *als_ops.build_grouped_edges(i, u, r, n_items))]
        runs = [
            als_ops.als_run_grouped(*dev, x0, y0, n_users, n_items, iters, 0.1,
                                    40.0, True, gather_kernel=g)
            for g in ("xla", "pallas")
        ]
        for a, b in zip(*runs):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


class TestStreamedALSTpu:
    def test_streamed_matches_in_memory_compiled(self, rng):
        """The host-chunked streamed ALS (ops/als_stream.py) on the real
        chip: per-chunk moment accumulation + flat-carry solve must match
        the one-program in-memory grouped run (compiled lowerings of the
        donated-carry segment-sum differ from the CPU suite's)."""
        import jax.numpy as jnp

        from oap_mllib_tpu.ops import als_ops, als_stream

        n_users, n_items, nnz, rank, iters = 300, 200, 20_000, 6, 3
        u = rng.integers(0, n_users, nnz).astype(np.int64)
        i = rng.integers(0, n_items, nnz).astype(np.int64)
        r = (rng.random(nnz) * 4 + 1).astype(np.float32)
        x0 = (rng.normal(size=(n_users, rank)) * 0.1).astype(np.float32)
        y0 = (rng.normal(size=(n_items, rank)) * 0.1).astype(np.float32)
        by_user = als_ops.build_grouped_edges(u, i, r, n_users)
        by_item = als_ops.build_grouped_edges(i, u, r, n_items)
        dev = [jnp.asarray(a) for a in (*by_user, *by_item)]
        xm, ym = als_ops.als_run_grouped(
            *dev, jnp.asarray(x0), jnp.asarray(y0),
            n_users, n_items, iters, 0.1, 5.0, True,
        )
        xs, ys = als_stream.als_run_streamed(
            by_user, by_item, x0, y0, n_users, n_items, iters, 0.1, 5.0,
            True,
        )
        np.testing.assert_allclose(np.asarray(xm), xs, atol=2e-4)
        np.testing.assert_allclose(np.asarray(ym), ys, atol=2e-4)
