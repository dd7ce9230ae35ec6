"""The data-parallel Lloyd (ops/kmeans_ops.lloyd_run on a mesh): what a
``KMeans.fit`` runs on any mesh of more than one device.

Every device accumulates its own row shard with the one-device accumulate
and the moments are all-reduced each iteration.  These tests tie that route
to the repo's plain float32 reference (``fallback/kmeans_np.py``) and to the
one-device program, on the 8-device virtual CPU mesh, where the walk is its
schedule-identical XLA scan (``kmeans_kernel._xla_walk``) and the default
dispatch the chunked XLA accumulate.  The compiled kernel inside the same
``shard_map`` is compiled for a described v5e:2x2 in
``tests/test_tpu_compile.py`` and run by the benchmark's four-chip cell.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oap_mllib_tpu import KMeans
from oap_mllib_tpu.config import get_config, set_config
from oap_mllib_tpu.fallback.kmeans_np import lloyd_np, predict_np
from oap_mllib_tpu.models import kmeans as kmeans_mod
from oap_mllib_tpu.ops import kmeans_ops
from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk
from oap_mllib_tpu.parallel.mesh import data_sharding, get_mesh
from oap_mllib_tpu.telemetry import metrics as tm

K, D = 5, 12


def _blobs(rng, n, k=K, d=D, spread=0.3):
    """(float32 blobs far apart against float32's step, their labels)."""
    proto = (rng.standard_normal((k, d)) * 4.0).astype(np.float32)
    labels = rng.integers(k, size=n)
    x = proto[labels] + spread * rng.standard_normal((n, d))
    return x.astype(np.float32), labels


def _start(x, labels, k=K):
    """One row of every blob.  A start that splits a blob puts the border
    between two centres through its dense middle, where some row sits
    within float32 rounding of a tie and changes sides when the sums are
    added in another order; with a centre a blob no row is near a tie and
    every route assigns every row alike."""
    return x[[int(np.flatnonzero(labels == j)[0]) for j in range(k)]]


def _separating_seed(labels, k=K):
    """A seed whose ``init_random`` draws one row of every blob (the same
    reason), so a fit through the public entry starts like ``_start``."""
    for seed in range(1000):
        idx = np.random.default_rng(seed).choice(len(labels), k, replace=False)
        if len(set(labels[idx])) == k:
            return seed
    raise AssertionError("no separating seed")


def _mesh_of(monkeypatch, n_devices):
    """Route ``KMeans.fit`` onto the first ``n_devices`` virtual devices
    through the normal entry (the estimator asks ``get_mesh()``)."""
    mesh = get_mesh(n_devices=n_devices)
    monkeypatch.setattr(kmeans_mod, "get_mesh", lambda: mesh)
    return mesh


def _sharded_run(x, w, c0, n_devices, max_iter=15, tol=0.0, **kw):
    mesh = get_mesh(n_devices=n_devices)
    xs = jax.device_put(x, data_sharding(mesh, 2))
    ws = jax.device_put(w, data_sharding(mesh, 1))
    out = kmeans_ops.lloyd_run(
        xs, ws, jnp.asarray(c0), max_iter, jnp.asarray(tol, jnp.float32),
        mesh=mesh, data_axis=get_config().data_axis, **kw,
    )
    return [np.asarray(o) for o in out]


class TestAgainstThePlainReference:
    """(a) a fit on a 4-device mesh, through ``KMeans.fit``, against
    ``kmeans_np.lloyd_np`` from the same initial centres."""

    def test_fit_on_four_devices_matches_kmeans_np(self, rng, monkeypatch):
        x, labels = _blobs(rng, 4096)
        seed = _separating_seed(labels)
        _mesh_of(monkeypatch, 4)
        model = KMeans(
            k=K, max_iter=15, tol=1e-4, seed=seed, init_mode="random"
        ).fit(x)
        s = model.summary
        # the estimator's random init is k rows drawn from the seed
        c0 = kmeans_ops.init_random(x, len(x), K, seed)
        ref_c, ref_iter, ref_cost = lloyd_np(x, c0, 15, 1e-4)
        # centres are ratios of sums of <= 4096 float32 rows of size ~10:
        # 1e-5 absolute is ~10 float32 steps at that size
        np.testing.assert_allclose(model.cluster_centers_, ref_c, atol=1e-5)
        # the cost: each row's term is a float32 difference of |x|^2+|c|^2
        # (~300) that leaves ~1 (12 dims x 0.3^2), so ~3e-5 relative a row,
        # less in the sum; 1e-4 leaves room for the shards' summation order
        np.testing.assert_allclose(s.training_cost, ref_cost, rtol=1e-4)
        # separated blobs: no row near a tie, so sizes and the iteration
        # count are exact
        sizes = np.bincount(predict_np(x, ref_c), minlength=K)
        np.testing.assert_array_equal(np.asarray(s.cluster_sizes), sizes)
        assert s.num_iter == ref_iter
        assert s.timings.root.node("lloyd_loop").attrs["shards"] == 4


class TestSharesAddUp:
    """(b) the four shards' ``(sums, counts, cost)`` summed on the host are
    the uncut one-device accumulate over the whole table."""

    @pytest.mark.parametrize("route", ["walk", "xla"])
    def test_moments_of_the_shards_sum_to_the_whole(self, rng, route):
        x, labels = _blobs(rng, 4096)
        w = np.ones((len(x),), np.float32)
        c = _start(x, labels)

        def accumulate(xb, wb):
            if route == "walk":
                # the walk as the CPU runs it: _xla_walk on padded operands
                return kk.lloyd_accumulate_walk(
                    jnp.asarray(xb), jnp.asarray(wb), jnp.asarray(c),
                    tile_rows=256,
                )
            return kmeans_ops._accumulate(
                jnp.asarray(xb), jnp.asarray(wb), jnp.asarray(c)
            )

        whole = [np.asarray(a, np.float64) for a in accumulate(x, w)]
        parts = [
            [np.asarray(a, np.float64) for a in accumulate(xb, wb)]
            for xb, wb in zip(np.split(x, 4), np.split(w, 4))
        ]
        sums, counts, cost = (sum(p[i] for p in parts) for i in range(3))
        # counts are small integers in float32: exact.  Sums and cost are
        # the same float32 terms added in another order: float32 rounding
        # of sums of ~1000 terms of size ~10
        np.testing.assert_array_equal(counts, whole[1])
        np.testing.assert_allclose(sums, whole[0], rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(cost, whole[2], rtol=1e-5)


class TestDeviceCounts:
    """(c) 1, 2, 4 and 8 devices give the same centres from the same
    start — to a bound, not bitwise: the shards' float32 sums are added in
    another order (PERF.md section 6, PR 21 lesson 4)."""

    @pytest.mark.parametrize("walk", [False, True], ids=["xla", "walk"])
    def test_same_centres_on_1_2_4_8_devices(self, rng, walk):
        x, labels = _blobs(rng, 4096)
        w = np.ones((len(x),), np.float32)
        c0 = _start(x, labels)
        kw = dict(accumulate="pallas" if walk else "xla", tile_rows=256)
        one = [np.asarray(o) for o in kmeans_ops.lloyd_run(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(c0), 15,
            jnp.asarray(0.0, jnp.float32), **kw,
        )]
        for n_devices in (1, 2, 4, 8):
            c, it, cost, counts = _sharded_run(x, w, c0, n_devices, **kw)
            # 1e-5 absolute on centres of size ~10 (a few float32 steps of
            # reordered sums); separated blobs keep assignments identical,
            # so counts and the iteration count are exact
            np.testing.assert_allclose(c, one[0], atol=1e-5)
            np.testing.assert_allclose(cost, one[2], rtol=1e-5)
            np.testing.assert_array_equal(counts, one[3])
            assert int(it) == int(one[1])


class TestRaggedAndEmptyShards:
    """(d) rows that do not divide by the shard count, and a shard that
    holds only padding."""

    def test_rows_that_do_not_divide(self, rng, monkeypatch):
        x, labels = _blobs(rng, 1003)  # on 4 devices: padded, masked
        seed = _separating_seed(labels)
        _mesh_of(monkeypatch, 4)
        model = KMeans(
            k=K, max_iter=10, tol=0.0, seed=seed, init_mode="random"
        ).fit(x)
        c0 = kmeans_ops.init_random(x, len(x), K, seed)
        ref_c, _, ref_cost = lloyd_np(x, c0, 10, 0.0)
        np.testing.assert_allclose(model.cluster_centers_, ref_c, atol=1e-5)
        np.testing.assert_allclose(
            model.summary.training_cost, ref_cost, rtol=1e-4
        )
        assert int(np.sum(model.summary.cluster_sizes)) == 1003

    @pytest.mark.parametrize("walk", [False, True], ids=["xla", "walk"])
    def test_a_shard_of_padding_only(self, rng, walk):
        # 300 rows padded to 4 x 256: devices 2 and 3 hold weight-0 rows
        # only and must add nothing to the moments
        x, labels = _blobs(rng, 300)
        c0 = _start(x, labels)
        xp = np.zeros((1024, D), np.float32)
        xp[:300] = x
        wp = np.zeros((1024,), np.float32)
        wp[:300] = 1.0
        c, it, cost, counts = _sharded_run(
            xp, wp, c0, 4, max_iter=10,
            accumulate="pallas" if walk else "xla", tile_rows=256,
        )
        ref_c, _, ref_cost = lloyd_np(x, c0, 10, 0.0)
        np.testing.assert_allclose(c, ref_c, atol=1e-5)
        np.testing.assert_allclose(cost, ref_cost, rtol=1e-4)
        assert counts.sum() == 300


class TestCounters:
    """(e) ``summary.kernel``, ``shards``, ``reduce_bytes`` and the
    ``oap_collective_ops_total`` count are what the shapes say."""

    def test_what_a_fit_on_the_mesh_reports(self, rng):
        x, _ = _blobs(rng, 4096)
        before = tm.snapshot().get("oap_collective_ops_total", {}).get(
            "op=psum", 0
        )
        model = KMeans(k=K, max_iter=7, tol=0.0, seed=3).fit(x)  # 8 devices
        s = model.summary
        assert s.kernel == "xla"  # off the TPU the dispatch never walks
        n = s.num_iter  # a fit that stops moving stops early, even at tol 0
        assert 1 <= n <= 7
        root = s.timings.root
        loop = root.node("lloyd_loop")
        assert loop.attrs["shards"] == 8
        assert loop.attrs["rows_per_shard"] == 4096 // 8
        # n iterations of (k, d) sums + (k,) counts, then counts + cost
        assert loop.attrs["reduce_bytes"] == (n * (K * D + K) + K + 1) * 4
        assert loop.attrs["collectives"]["psum"]["ops"] == n + 1
        assert loop.attrs["collectives"]["psum"]["bytes"] == (
            8 * loop.attrs["reduce_bytes"]  # each of this process's devices
        )
        after = tm.snapshot()["oap_collective_ops_total"]["op=psum"]
        assert after - before == n + 1
        assert root.node("table_convert/upload").attrs["shards"] == 8
        assert root.node("init_centers/rounds").attrs["shards"] == 8

    def test_what_the_rounds_fold_on_the_mesh(self, rng, monkeypatch):
        """k = 160 gives 640 slots a round in two chunks of 320 and about
        320 picks: the ``rounds`` span counts the chunks each round's
        picks reached, by the device's own formula."""
        k = 160
        cap, chunk = 4 * k, kmeans_ops._slot_chunk_size(4 * k)
        assert (cap, chunk) == (640, 320)
        x, _ = _blobs(rng, 4096, k=40)
        filled = []
        round_ = kmeans_ops._pll_round

        def spy(*a, **kw):
            out = round_(*a, **kw)
            assert a[-2:] == (cap, chunk)
            filled.append(int((np.asarray(out[1]) > 0).sum()))
            return out

        monkeypatch.setattr(kmeans_ops, "_pll_round", spy)
        model = KMeans(k=k, max_iter=2, seed=3).fit(x)  # 8 devices
        attrs = model.summary.timings.root.node("init_centers/rounds").attrs
        assert attrs["shards"] == 8 and attrs["rounds"] == len(filled) == 2
        assert all(0 < f < cap for f in filled)
        assert attrs["slots_filled"] == sum(filled)
        assert attrs["slot_chunks"] == sum(-(-f // chunk) for f in filled)
        assert attrs["slot_chunks_cap"] == 2 * (cap // chunk)
        assert attrs["slot_chunks"] <= attrs["slot_chunks_cap"]
        # picks near l = 2k = 320 of 640: a round folds one chunk or two
        assert 2 <= attrs["slot_chunks"] <= 4

    def test_the_walk_reduces_its_lane_padded_blocks(self):
        # k=1000, d=256 (the benchmark's cell): 1024 x 256 sums and 1024
        # counts an iteration
        assert kmeans_ops.lloyd_reduce_bytes(1000, 256, 4, 20, True) == (
            20 * (1024 * 256 + 1024) + 1024 + 1
        ) * 4
        assert kmeans_ops.lloyd_reduce_bytes(1000, 256, 4, 20, False) == (
            20 * (1000 * 256 + 1000) + 1000 + 1
        ) * 4

    def test_one_device_mesh_keeps_the_one_device_program(self, rng,
                                                          monkeypatch):
        """A mesh of one device emits no collective and books none."""
        x, _ = _blobs(rng, 1024)
        _mesh_of(monkeypatch, 1)
        model = KMeans(k=K, max_iter=5, tol=0.0, seed=3).fit(x)
        loop = model.summary.timings.root.node("lloyd_loop")
        assert "shards" not in loop.attrs
        assert "collectives" not in loop.attrs

    def test_xla_forced_on_a_model_axis_runs_the_data_parallel_program(
            self, rng):
        """``kmeans_kernel="xla"`` on a (data, model) mesh: the same
        shard_map over the data axis, the model axis holding replicas."""
        x, labels = _blobs(rng, 2048)
        seed = _separating_seed(labels)
        plain = KMeans(
            k=K, max_iter=8, tol=0.0, seed=seed, init_mode="random"
        ).fit(x)
        set_config(model_parallel=2, kmeans_kernel="xla")
        model = KMeans(
            k=K, max_iter=8, tol=0.0, seed=seed, init_mode="random"
        ).fit(x)
        assert model.summary.kernel == "xla"
        loop = model.summary.timings.root.node("lloyd_loop")
        assert loop.attrs["shards"] == 4
        np.testing.assert_allclose(
            model.cluster_centers_, plain.cluster_centers_, atol=1e-5
        )
