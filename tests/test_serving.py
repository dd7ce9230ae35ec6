"""Serving-plane tests (ISSUE 13): registry pinning, bucketed
micro-batching, full-sweep top-k (streamed + factor-sharded ring), and
replica availability.

Parity contracts under test:

- registry-served results are BIT-identical to direct model calls for
  all three estimators (same pinned weights, same programs);
- bucketed batches match at 1e-6 across jittered request sizes (ids
  exactly — per-row scoring is independent of the batch's padding);
- the serving sweep matches ``recommend_for_all_users`` exactly (ids
  AND score bits — same chunk widths, same programs);
- the ring-merged sharded sweep matches the single-device reference on
  the 8-device pseudo-mesh, including deliberate score ties (the
  lexicographic merge reproduces lax.top_k's lowest-id tie rule).
"""

from __future__ import annotations

import numpy as np
import pytest

from oap_mllib_tpu import serving
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.models.als import ALS, ALSModel
from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.models.pca import PCA
from oap_mllib_tpu.serving import batcher, sweep
from oap_mllib_tpu.telemetry import metrics as tm
from oap_mllib_tpu.utils import progcache


@pytest.fixture(autouse=True)
def _clear_registry():
    from oap_mllib_tpu.serving import registry as reg

    reg.clear()
    yield
    reg.clear()


def _kmeans_model(rng, n=400, d=12, k=5):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return KMeans(k=k, seed=3, max_iter=4).fit(x), x


def _als_model(rng, nu=60, ni=48, rank=5):
    u = rng.integers(0, nu, size=3000)
    i = rng.integers(0, ni, size=3000)
    r = rng.normal(size=3000).astype(np.float32)
    return ALS(rank=rank, max_iter=2, seed=1).fit(
        u, i, r, n_users=nu, n_items=ni
    )


class TestRegistry:
    def test_serve_is_keyed_like_progcache(self, rng):
        m, _ = _kmeans_model(rng)
        h1 = serving.serve(m)
        h2 = serving.serve(m)
        assert h1 is h2  # same model object -> same handle, no re-pin
        assert serving.unserve(m)
        assert not serving.unserve(m)

    def test_serve_rejects_unknown_surface(self):
        with pytest.raises(TypeError, match="cannot serve"):
            serving.serve(object())

    def test_served_bit_identical_all_estimators(self, rng):
        x = rng.normal(size=(300, 10)).astype(np.float32)
        km = KMeans(k=4, seed=2, max_iter=3).fit(x)
        hk = serving.serve(km)
        assert np.array_equal(hk.predict(x[:97]), km.predict(x[:97]))
        assert np.array_equal(hk.transform(x[:31]), km.transform(x[:31]))

        pca = PCA(k=3).fit(x)
        hp = serving.serve(pca)
        assert np.array_equal(hp.transform(x[:53]), pca.transform(x[:53]))

        als = _als_model(rng)
        ha = serving.serve(als)
        ids_m, s_m = als.recommend_for_users(
            np.arange(20), 6, with_scores=True
        )
        ids_h, s_h = ha.recommend_for_users(
            np.arange(20), 6, with_scores=True
        )
        assert np.array_equal(ids_m, ids_h)
        np.testing.assert_array_equal(s_m, s_h)
        assert np.array_equal(
            ha.recommend_for_all_users(5),
            als.recommend_for_all_users(5),
        )

    def test_zero_reupload_and_zero_recompile_on_repeat(self, rng):
        """Satellite: repeat scoring calls re-upload nothing (the pinned
        device buffer is the SAME object) and compile nothing (XLA
        ground truth)."""
        m, x = _kmeans_model(rng)
        m.predict(x[:100])  # warm: pin + compile
        pinned = m._dev_cache["centers"][1]
        before = progcache.xla_compile_count()
        m.predict(x[:100])
        m.predict(x[:100])
        assert progcache.xla_compile_count() - before == 0
        assert m._dev_cache["centers"][1] is pinned

    def test_transfer_guard_clean_request_path(self, rng):
        """The request path stages everything EXPLICITLY: a served
        predict under the transfer sanitizer's disallow guard raises on
        any implicit transfer — passing means zero hidden re-uploads."""
        from oap_mllib_tpu.utils import sanitizers

        m, x = _kmeans_model(rng)
        h = serving.serve(m)
        h.predict(x[:64])  # warm outside the guard
        set_config(sanitizers="transfer")
        try:
            with sanitizers.transfer_scope():
                ids = batcher.assign_kmeans(h.centers_dev, x[:64])
        finally:
            set_config(sanitizers="")
        assert ids.shape == (64,)

    def test_refit_invalidates_pin(self, rng):
        m, x = _kmeans_model(rng)
        m.predict(x[:10])
        old = m._dev_cache["centers"][1]
        m.cluster_centers_ = m.cluster_centers_.copy()  # a "refit"
        m.predict(x[:10])
        assert m._dev_cache["centers"][1] is not old

    def test_als_targets_pinned_across_chunks_and_calls(self, rng):
        """Satellite: one sweep chunks the query side but pins the
        target table once — and the pin survives across calls."""
        als = _als_model(rng)
        als.recommend_for_all_users(4)  # pins targets:item
        pinned = als._dev_cache["targets:item"][1]
        before = progcache.xla_compile_count()
        ids1 = als.recommend_for_all_users(4)
        ids2, _ = als._top_k_scores(
            als.user_factors_, als.item_factors_, 4, row_chunk=7
        )
        assert als._dev_cache["targets:item"][1] is pinned
        assert progcache.xla_compile_count() - before <= 2  # tail buckets
        ids3 = als.recommend_for_all_users(4)
        assert np.array_equal(ids1, ids3)

    def test_predict_many_coalesces(self, rng):
        m, x = _kmeans_model(rng)
        h = serving.serve(m)
        parts = h.predict_many([x[:7], x[7:20], x[20:21]])
        direct = m.predict(x[:21])
        assert np.array_equal(np.concatenate(parts), direct)
        assert h.requests == 3
        # the coalesced flush left the queue-depth gauge back at zero
        assert tm.gauge("oap_serve_queue_depth").value == 0

    def test_warmup_then_jittered_storm_compiles_nothing(self, rng):
        m, x = _kmeans_model(rng, n=700)
        h = serving.serve(m)
        h.warmup(512)
        before = progcache.xla_compile_count()
        for s in rng.integers(1, 512, size=50):
            h.predict(x[: int(s)])
        assert progcache.xla_compile_count() - before == 0

    def test_serving_summary_block(self, rng):
        m, x = _kmeans_model(rng)
        h = serving.serve(m)
        h.predict(x[:30])
        block = serving.serving_summary()
        assert block["models_pinned"] == 1
        assert block["requests"] >= 1
        assert block["latency_p50_s"] > 0
        assert block["latency_p99_s"] >= block["latency_p50_s"]


class TestBatcher:
    def test_bucket_batch_pads_to_geometric_bucket(self):
        x = np.ones((9, 3), np.float32)
        padded, n = batcher.bucket_batch(x)
        assert n == 9
        assert padded.shape == (16, 3)  # 8 -> 16 geometric series
        assert (padded[9:] == 0).all()

    def test_bucket_batch_off_restores_exact_padding(self):
        set_config(shape_bucketing="off")
        padded, n = batcher.bucket_batch(np.ones((9, 3), np.float32))
        assert padded.shape == (16, 3)  # multiple-of-8 exact padding

    def test_bucketed_parity_across_jittered_sizes(self, rng):
        """Bucketed scoring matches the unpadded result at 1e-6 for
        every size in a jittered storm (ids exactly; PCA projections
        to 1e-6)."""
        m, x = _kmeans_model(rng, n=600)
        pca = PCA(k=3).fit(x)
        from oap_mllib_tpu.fallback.kmeans_np import predict_np

        comp = pca.components_
        for s in rng.integers(1, 600, size=12):
            s = int(s)
            ids = m.predict(x[:s])
            assert np.array_equal(
                ids, predict_np(x[:s].astype(np.float64),
                                m.cluster_centers_.astype(np.float64),
                                "euclidean")
            ), f"ids diverge at size {s}"
            proj = pca.transform(x[:s])
            np.testing.assert_allclose(
                proj, x[:s] @ comp, atol=1e-5, rtol=1e-5
            )

    def test_warm_sizes_cover_the_range(self):
        sizes = batcher.warm_sizes(1000)
        assert sizes[-1] >= 1000
        assert sizes == sorted(set(sizes))

    def test_serving_precision_typo_raises(self, rng):
        m, x = _kmeans_model(rng)
        set_config(serving_precision="fp8")
        with pytest.raises(ValueError, match="serving_precision"):
            m.predict(x[:4])

    def test_serving_precision_override_resolves(self):
        set_config(serving_precision="tf32")
        pol = batcher.resolve_policy("kmeans")
        assert pol.name == "tf32"
        set_config(serving_precision="")
        assert batcher.resolve_policy("kmeans").name == "f32"

    def test_serve_request_fault_site_drillable(self, rng):
        from oap_mllib_tpu.utils import faults

        m, x = _kmeans_model(rng)
        m.predict(x[:8])  # warm
        set_config(fault_spec="serve.request:fail=1")
        try:
            with pytest.raises(faults.FaultInjected):
                m.predict(x[:8])
            # the armed count is consumed: the next request answers
            assert m.predict(x[:8]).shape == (8,)
        finally:
            set_config(fault_spec="")
            faults.reset()


class TestChunkSourceScoring:
    def test_kmeans_chunksource_bit_identical_to_ndarray(self, rng):
        """Satellite: disk/stream-backed scoring routes through the SAME
        bucketed serving program — bit-identical labels."""
        from oap_mllib_tpu.data.stream import ChunkSource

        m, x = _kmeans_model(rng, n=500)
        direct = m.predict(x)
        src = ChunkSource.from_array(x, chunk_rows=96)
        assert np.array_equal(m.predict(src), direct)
        # two passes over the source add no compiled shapes
        before = progcache.xla_compile_count()
        assert np.array_equal(m.predict(src), direct)
        assert progcache.xla_compile_count() - before == 0

    def test_kmeans_disk_backed_scoring(self, rng, tmp_path):
        from oap_mllib_tpu.data import io as dio
        from oap_mllib_tpu.data.stream import ChunkSource

        m, x = _kmeans_model(rng, n=300)
        path = str(tmp_path / "table.npy")
        dio.atomic_save_npy(path, x)
        src = ChunkSource.from_npy(path, chunk_rows=64)
        assert np.array_equal(m.predict(src), m.predict(x))

    def test_pca_chunksource_matches_ndarray(self, rng):
        from oap_mllib_tpu.data.stream import ChunkSource

        x = rng.normal(size=(400, 9)).astype(np.float32)
        pca = PCA(k=4).fit(x)
        src = ChunkSource.from_array(x, chunk_rows=128)
        np.testing.assert_allclose(
            pca.transform(src), pca.transform(x), atol=1e-6
        )


def _host_als(rng, nu, ni, r=5):
    """A HOST-factor ALSModel (the streamed sweep path — fitted models
    on the suite's 8-device mesh come out block-sharded and take the
    ring path instead, covered by TestShardedSweep)."""
    return ALSModel(
        rng.normal(size=(nu, r)).astype(np.float32),
        rng.normal(size=(ni, r)).astype(np.float32),
    )


class TestSweep:
    def test_sweep_matches_model_exactly(self, rng):
        als = _host_als(rng, nu=150, ni=64)
        ids_m, s_m = als.recommend_for_all_users(9, with_scores=True)
        ids_s, s_s = sweep.recommend_for_all_users(
            als, 9, with_scores=True
        )
        assert np.array_equal(ids_m, ids_s)
        np.testing.assert_array_equal(s_m, s_s)  # bit parity

    def test_sweep_of_fitted_model_matches_model(self, rng):
        als = _als_model(rng, nu=100, ni=48)
        assert np.array_equal(
            sweep.recommend_for_all_users(als, 6),
            als.recommend_for_all_users(6),
        )

    def test_sweep_chunk_override_and_tail_bucket(self, rng):
        als = _host_als(rng, nu=101, ni=32)
        ref = als.recommend_for_all_users(5)
        ids = sweep.recommend_for_all_users(als, 5, chunk_rows=17)
        assert np.array_equal(ids, ref)

    def test_sweep_clamps_num_items(self, rng):
        als = _host_als(rng, nu=20, ni=8)
        ids = sweep.recommend_for_all_users(als, 99)
        assert ids.shape == (20, 8)

    def test_sweep_zero_k_and_negative(self, rng):
        als = _host_als(rng, nu=12, ni=8)
        assert sweep.recommend_for_all_users(als, 0).shape == (12, 0)
        with pytest.raises(ValueError, match=">= 0"):
            sweep.recommend_for_all_users(als, -1)

    def test_sweep_chunk_rows_config_negative_raises(self, rng):
        als = _host_als(rng, nu=12, ni=8)
        set_config(sweep_chunk_rows=-1)
        with pytest.raises(ValueError, match="sweep_chunk_rows"):
            sweep.recommend_for_all_users(als, 2)

    def test_sweep_streamed_is_chunk_invariant(self, rng):
        """Different chunk widths produce the same answer — the fold
        never depends on how the user table was sliced."""
        als = _host_als(rng, nu=90, ni=40)
        ref = sweep.recommend_for_all_users(als, 6, chunk_rows=90)
        for rows in (7, 13, 64):
            assert np.array_equal(
                sweep.recommend_for_all_users(als, 6, chunk_rows=rows),
                ref,
            )

    def test_sweep_large_table_bounded_memory(self, rng):
        """A 200k-user synthetic factor table sweeps with O(chunk)
        device footprint (the quadratic score matrix would be 200k x
        256 = 200 MB; chunks bound it to chunk x 256).  Spot-check
        parity on sampled rows against a direct top-k."""
        nu, ni, r, k = 200_000, 256, 8, 4
        uf = rng.normal(size=(nu, r)).astype(np.float32)
        itf = rng.normal(size=(ni, r)).astype(np.float32)
        m = ALSModel(uf, itf)
        ids = sweep.recommend_for_all_users(m, k, chunk_rows=8192)
        assert ids.shape == (nu, k)
        sample = rng.integers(0, nu, size=64)
        scores = uf[sample] @ itf.T
        expect = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        assert np.array_equal(ids[sample], expect)


# a sharded sweep's scores vs the one-device reference: the same dot
# products compiled for different device counts agree to the last ulp
# or so (observed 1.9e-7 relative), not bit for bit
_SCORE_RTOL = 1e-6


class TestShardedSweep:
    """Factor-sharded ring sweep on the 8-device pseudo-mesh: the live
    block layout serves without a host gather, and the ring-merged
    top-k matches the single-device reference — ids exactly, scores to
    ``_SCORE_RTOL`` (the per-block score matmuls of an 8-device program
    and the one-device program may round their last bit differently)."""

    def _sharded_als(self, rng, layout, nu=200, ni=96):
        set_config(als_item_layout=layout)
        u = rng.integers(0, nu, size=6000)
        i = rng.integers(0, ni, size=6000)
        r = rng.normal(size=6000).astype(np.float32)
        return ALS(rank=6, max_iter=2, seed=2).fit(
            u, i, r, n_users=nu, n_items=ni
        )

    def test_ring_sweep_matches_reference(self, rng):
        m = self._sharded_als(rng, "sharded")
        assert m._sharded_user is not None and m._sharded_item is not None
        ids, scores = sweep.recommend_for_all_users(
            m, 7, with_scores=True
        )
        ref = ALSModel(
            np.array(m.user_factors_), np.array(m.item_factors_)
        )
        ids_ref, s_ref = ref._top_k_scores(
            ref.user_factors_, ref.item_factors_, 7
        )
        assert np.array_equal(ids, ids_ref)
        np.testing.assert_allclose(scores, s_ref, rtol=_SCORE_RTOL)

    def test_replicated_item_sharded_user_sweep(self, rng):
        m = self._sharded_als(rng, "replicated")
        assert m._sharded_user is not None and m._sharded_item is None
        ids, scores = sweep.recommend_for_all_users(
            m, 5, with_scores=True
        )
        ref = ALSModel(
            np.array(m.user_factors_), np.array(m.item_factors_)
        )
        ids_ref, s_ref = ref._top_k_scores(
            ref.user_factors_, ref.item_factors_, 5
        )
        assert np.array_equal(ids, ids_ref)
        np.testing.assert_array_equal(scores, s_ref)

    def test_ring_merge_tie_breaking_matches_top_k(self, rng):
        """Deliberate cross-block score ties: duplicate item rows land
        in different ring blocks; the lexicographic merge must pick the
        LOWEST global id — exactly lax.top_k's tie rule on the
        unsharded reference."""
        from oap_mllib_tpu.parallel.mesh import get_mesh

        set_config(als_item_layout="sharded")
        mesh = get_mesh()
        nu, ni, r = 64, 80, 4
        uf = rng.normal(size=(nu, r)).astype(np.float32)
        base = rng.normal(size=(10, r)).astype(np.float32)
        itf = np.tile(base, (8, 1))  # every row duplicated across blocks
        ub, uoff, upp = sweep.shard_factors(uf, mesh)
        ib, ioff, ipp = sweep.shard_factors(itf, mesh)
        m = ALSModel(
            None, None,
            sharded_user=(ub, uoff, upp), sharded_item=(ib, ioff, ipp),
        )
        ids, scores = sweep.recommend_for_all_users(
            m, 12, with_scores=True
        )
        ref = ALSModel(uf, itf)
        ids_ref, s_ref = ref._top_k_scores(uf, itf, 12)
        assert np.array_equal(ids, ids_ref)
        np.testing.assert_array_equal(scores, s_ref)

    def test_shard_factors_roundtrip(self, rng):
        from oap_mllib_tpu.parallel.mesh import get_mesh

        f = rng.normal(size=(123, 6)).astype(np.float32)
        blocks, offsets, per = sweep.shard_factors(f, get_mesh())
        m = ALSModel(
            None, np.zeros((4, 6), np.float32),
            sharded_user=(blocks, offsets, per),
        )
        assert np.array_equal(m.user_factors_, f)


class TestEvictionReform:
    """ISSUE 18: a sharded sweep that loses a replica mid-flight either
    re-forms on the survivors' local layout (reform hook) or fails
    loudly naming the culprit crash records — never a silent hang."""

    def _host_tables(self, rng):
        uf = rng.normal(size=(40, 5)).astype(np.float32)
        itf = rng.normal(size=(32, 5)).astype(np.float32)
        return uf, itf

    def _local_model(self, uf, itf):
        return ALSModel(
            None, None,
            sharded_user=sweep.shard_factors_local(uf),
            sharded_item=sweep.shard_factors_local(itf),
        )

    def test_shard_factors_local_serves_bit_identical(self, rng):
        """Identical ids; scores to ``_SCORE_RTOL`` of the one-device
        reference (the survivors' layout is an 8-device program)."""
        uf, itf = self._host_tables(rng)
        ids, scores = sweep.recommend_for_all_users(
            self._local_model(uf, itf), 6, with_scores=True
        )
        ref = ALSModel(uf, itf)
        ids_ref, s_ref = ref._top_k_scores(uf, itf, 6)
        assert np.array_equal(ids, ids_ref)
        np.testing.assert_allclose(scores, s_ref, rtol=_SCORE_RTOL)

    def test_reform_hook_reforms_once_and_answers(self, rng, monkeypatch):
        from oap_mllib_tpu.utils import recovery

        uf, itf = self._host_tables(rng)
        real = sweep._sweep_sharded
        calls = {"n": 0}

        def dies_once(model, n, ws):
            calls["n"] += 1
            if calls["n"] == 1:
                raise recovery.CollectiveTimeoutError(
                    "peer died mid-sweep"
                )
            return real(model, n, ws)

        monkeypatch.setattr(sweep, "_sweep_sharded", dies_once)
        reforms0 = tm.family_total("oap_serve_sweep_reforms_total")
        reformed = []

        def reform(exc):
            reformed.append(exc)
            return self._local_model(uf, itf)

        ids, scores = sweep.recommend_for_all_users(
            self._local_model(uf, itf), 6, with_scores=True,
            reform=reform,
        )
        ref = ALSModel(uf, itf)
        ids_ref, s_ref = ref._top_k_scores(uf, itf, 6)
        assert np.array_equal(ids, ids_ref)
        np.testing.assert_allclose(scores, s_ref, rtol=_SCORE_RTOL)
        assert len(reformed) == 1
        assert isinstance(
            reformed[0], recovery.CollectiveTimeoutError
        )
        assert (
            tm.family_total("oap_serve_sweep_reforms_total")
            == reforms0 + 1
        )

    def test_reform_runs_once_then_raw_recovery_error(
        self, rng, monkeypatch
    ):
        # the re-formed sweep gets NO second reform: a hook that hands
        # back another doomed mesh surfaces the recovery error raw
        from oap_mllib_tpu.utils import recovery

        uf, itf = self._host_tables(rng)

        def always_dies(model, n, ws):
            raise recovery.CollectiveTimeoutError("still doomed")

        monkeypatch.setattr(sweep, "_sweep_sharded", always_dies)
        with pytest.raises(serving.ServeError) as ei:
            sweep.recommend_for_all_users(
                self._local_model(uf, itf), 6,
                reform=lambda exc: self._local_model(uf, itf),
            )
        assert ei.value.reason == "eviction"

    def test_no_reform_hook_names_the_crash_records(
        self, rng, monkeypatch, tmp_path
    ):
        from oap_mllib_tpu.utils import recovery

        set_config(crash_dir=str(tmp_path))
        recovery.write_crash_record(
            "serve.heartbeat", "collective_timeout", "peer preempted"
        )

        def dead_mesh(model, n, ws):
            raise recovery.PeerAbortError("mesh spans a dead peer")

        monkeypatch.setattr(sweep, "_sweep_sharded", dead_mesh)
        uf, itf = self._host_tables(rng)
        with pytest.raises(serving.ServeError) as ei:
            sweep.recommend_for_all_users(self._local_model(uf, itf), 6)
        err = ei.value
        assert err.reason == "eviction"
        assert isinstance(err.__cause__, recovery.PeerAbortError)
        assert len(err.crash_records) == 1
        assert "crash" in str(err)  # the culprit record is NAMED

    def test_list_crash_records_filters_and_sorts(self, tmp_path):
        from oap_mllib_tpu.utils import recovery

        set_config(crash_dir=str(tmp_path))
        recovery.write_crash_record("site.a", "unclassified", "x")
        (tmp_path / "serve.drain.done.rank0.json").write_text("{}")
        (tmp_path / "notes.txt").write_text("ignore")
        recs = recovery.list_crash_records(str(tmp_path))
        assert len(recs) == 1
        assert recs[0].endswith(".json") and "crash" in recs[0]
        assert recovery.list_crash_records(
            str(tmp_path / "missing")
        ) == []


class TestHA:
    def test_heartbeat_single_process_view(self):
        view = serving.heartbeat(requests=7, queue_depth=2)
        assert view["world"] == 1
        assert view["requests"] == [7]
        assert view["queue_depth"] == [2]

    def test_replica_guard_absorbs_recovery_errors(self):
        from oap_mllib_tpu.utils import recovery

        guard = serving.ReplicaGuard()
        before = tm.family_total("oap_serve_evictions_total")
        with guard.leg():
            raise recovery.CollectiveTimeoutError(
                "peer missed deadline", op="process_allgather",
                axis="host", elapsed_s=10.0,
            )
        assert guard.local_only
        assert guard.evictions == 1
        assert isinstance(
            guard.last_error, recovery.CollectiveTimeoutError
        )
        assert tm.family_total("oap_serve_evictions_total") == before + 1

    def test_replica_guard_propagates_other_errors(self):
        guard = serving.ReplicaGuard()
        with pytest.raises(ValueError):
            with guard.leg():
                raise ValueError("a genuine bug")
        assert not guard.local_only


class TestMetricsQuantile:
    def test_histogram_quantile_bucket_upper_bounds(self):
        h = tm.Histogram(bounds=(1.0, 4.0, 16.0))
        for v in (0.5, 0.5, 3.0, 10.0):
            h.observe(v)
        assert tm.histogram_quantile(h, 0.5) == 1.0
        assert tm.histogram_quantile(h, 0.99) == 16.0
        with pytest.raises(ValueError):
            tm.histogram_quantile(h, -0.1)
        with pytest.raises(ValueError):
            tm.histogram_quantile(h, 1.01)

    def test_quantile_empty_histogram(self):
        h = tm.Histogram(bounds=(1.0, 2.0))
        for q in (0.0, 0.5, 1.0):
            assert tm.histogram_quantile(h, q) == 0.0

    def test_quantile_q0_is_min_estimate(self):
        # q=0 names the lowest NON-EMPTY bucket, not bounds[0]
        h = tm.Histogram(bounds=(1.0, 4.0, 16.0))
        h.observe(3.0)
        h.observe(10.0)
        assert tm.histogram_quantile(h, 0.0) == 4.0

    def test_quantile_single_bucket_mass(self):
        # all mass in one bucket: every quantile names that bucket
        h = tm.Histogram(bounds=(1.0, 4.0, 16.0))
        for _ in range(7):
            h.observe(2.0)
        for q in (0.0, 0.25, 0.5, 1.0):
            assert tm.histogram_quantile(h, q) == 4.0

    def test_quantile_overflow_clamps_to_last_finite_bound(self):
        # mass past the largest finite bound has no upper witness:
        # q=0, q=1, and everything between clamp to bounds[-1]
        h = tm.Histogram(bounds=(1.0, 4.0))
        h.observe(100.0)
        for q in (0.0, 0.5, 1.0):
            assert tm.histogram_quantile(h, q) == 4.0

    def test_quantile_q1_is_max_bucket(self):
        h = tm.Histogram(bounds=(1.0, 4.0, 16.0))
        h.observe(0.5)
        h.observe(12.0)
        assert tm.histogram_quantile(h, 1.0) == 16.0
