"""Compile-amortization subsystem: program-cache registry, shape
bucketing, persistent-cache wiring, and the cross-fit program-reuse
contract (ISSUE 2).

The reuse probes assert on REAL XLA backend compiles
(progcache.xla_compile_count, the jax monitoring event) — not just the
registry's own counters — so a regression that re-traces programs
cannot hide behind correct bookkeeping."""

import os

import numpy as np
import pytest

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data.bucketing import bucket_factor, bucket_rows
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.progcache import ProgramCache
from oap_mllib_tpu.utils.timing import Timings


class TestRegistry:
    def test_get_or_build_caches_and_counts(self):
        pc = ProgramCache()
        built = []

        def build():
            built.append(1)
            return "prog"

        assert pc.get_or_build("algo", ("k",), build) == "prog"
        assert pc.get_or_build("algo", ("k",), build) == "prog"
        assert built == [1]
        s = pc.stats()
        assert s["hits"] == 1 and s["misses"] == 1
        assert s["by_algo"]["algo"] == {
            "hits": 1, "misses": 1, "evictions": 0,
        }

    def test_lru_eviction_counts(self):
        pc = ProgramCache(maxsize=2)
        for k in ("a", "b", "c"):
            pc.get_or_build("algo", (k,), lambda k=k: k)
        s = pc.stats()
        assert s["evictions"] == 1
        # "a" was evicted; rebuilding it is a miss again
        pc.get_or_build("algo", ("a",), lambda: "a2")
        assert pc.stats()["by_algo"]["algo"]["misses"] == 4

    def test_note_first_seen_then_hit(self):
        pc = ProgramCache()
        assert pc.note("x", (1,)) is True
        assert pc.note("x", (1,)) is False
        assert pc.note("x", (2,)) is True
        s = pc.stats()
        assert s["misses"] == 2 and s["hits"] == 1
        assert s["hit_rate"] == pytest.approx(1 / 3)

    def test_delta_is_per_fit(self):
        pc = ProgramCache()
        pc.note("x", (1,))
        before = pc.stats()
        pc.note("x", (1,))
        pc.note("x", (3,))
        # module-level delta() works off the module singleton; emulate
        # the arithmetic directly on this instance's snapshots
        now = pc.stats()
        d = {k: now[k] - before[k] for k in ("hits", "misses")}
        assert d == {"hits": 1, "misses": 1}

    def test_launch_books_compile_then_execute(self):
        t = Timings()
        with progcache.launch("t.algo", ("unique-key-1",), t, "phase"):
            pass
        with progcache.launch("t.algo", ("unique-key-1",), t, "phase"):
            pass
        sub = t.subphases("phase")
        assert "compile" in sub and "execute" in sub
        split = t.compile_split("phase")
        assert split is not None and split["compile"] >= 0.0

    def test_launch_record_execute_off_skips_hit_walls(self):
        t = Timings()
        for _ in range(3):
            with progcache.launch(
                "t.algo2", ("unique-key-2",), t, "phase",
                record_execute=False,
            ):
                pass
        sub = t.subphases("phase")
        assert "compile" in sub and "execute" not in sub

    def test_compile_split_none_without_launches(self):
        assert Timings().compile_split("phase") is None


class TestBucketing:
    def test_geometric_series(self):
        assert bucket_rows(1, 256) == 256
        assert bucket_rows(300, 256) == 512
        assert bucket_rows(512, 256) == 512
        assert bucket_rows(513, 256) == 1024
        assert bucket_rows(100) == 128
        assert bucket_rows(128) == 128

    def test_off_restores_exact_padding(self):
        set_config(shape_bucketing="off")
        assert bucket_rows(300, 256) == 512  # exact multiple of 256
        assert bucket_rows(700, 256) == 768  # NOT a power-of-two bucket
        assert bucket_rows(7) == 7

    def test_custom_factor(self):
        # gentler growth: buckets step ~1.25x instead of doubling
        assert bucket_rows(1000, 256, factor=1.25) == 1024
        assert bucket_rows(700, 256, factor=1.25) == 768

    def test_bad_values_raise(self):
        with pytest.raises(ValueError, match="shape_bucketing"):
            bucket_factor("bogus")
        with pytest.raises(ValueError, match="> 1"):
            bucket_factor("0.5")
        set_config(shape_bucketing="nope")
        with pytest.raises(ValueError, match="shape_bucketing"):
            bucket_rows(100, 256)

    def test_table_rows_land_on_buckets(self, rng):
        from oap_mllib_tpu.data.table import DenseTable
        from oap_mllib_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
        m0 = mesh.shape[mesh.axis_names[0]] * 256
        x = rng.normal(size=(2 * m0 + 100, 4)).astype(np.float32)
        t_on = DenseTable.from_numpy(x, mesh)
        assert t_on.n_padded == 4 * m0  # bucket, not the exact 3*m0
        assert t_on.n_rows == x.shape[0]
        np.testing.assert_array_equal(t_on.to_numpy(), x)
        assert float(np.asarray(t_on.mask)[x.shape[0]:].max(initial=0)) == 0

        set_config(shape_bucketing="off")
        t_off = DenseTable.from_numpy(x, mesh)
        assert t_off.n_padded == 3 * m0  # exact padding restored
        np.testing.assert_array_equal(t_off.to_numpy(), x)

    def test_chunk_rows_bucket(self, rng):
        from oap_mllib_tpu.data.stream import ChunkSource

        x = rng.normal(size=(250, 3))
        src = ChunkSource.from_array(x, chunk_rows=100)
        assert src.chunk_rows == 128
        np.testing.assert_allclose(
            np.concatenate([c[:v] for c, v in src]), x
        )
        set_config(shape_bucketing="off")
        assert ChunkSource.from_array(x, chunk_rows=100).chunk_rows == 100


@pytest.fixture
def jax_cache_restore():
    """Persistent-cache tests mutate process-global jax config; restore."""
    import jax

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_applied = progcache._persist_applied
    yield
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_compilation_cache_dir", prev_dir)
    cc.reset_cache()  # jax pins its cache object to the first dir it saw
    progcache._persist_applied = prev_applied


class TestPersistentCache:
    def test_env_dir_stands_and_a_differing_config_dir_raises(
            self, tmp_path, jax_cache_restore, monkeypatch):
        """Where JAX_COMPILATION_CACHE_DIR is set, the environment owns
        the process's cache: no directory is set from here, and a Config
        value naming another one is an error, not an override."""
        import jax

        from oap_mllib_tpu.utils.dispatch import should_accelerate

        env_dir = str(tmp_path / "env-cache")
        monkeypatch.setenv(progcache.CACHE_ENV, env_dir)
        monkeypatch.setattr(progcache, "_persist_applied", None)
        before = jax.config.jax_compilation_cache_dir
        # empty Config: nothing at all is touched
        assert should_accelerate("KMeans", True)
        assert jax.config.jax_compilation_cache_dir == before
        # the same directory: accepted, still no directory update
        set_config(compilation_cache_dir=env_dir)
        assert should_accelerate("KMeans", True)
        assert jax.config.jax_compilation_cache_dir == before
        assert progcache._persist_applied == env_dir
        # another directory: refused at fit entry
        set_config(compilation_cache_dir=str(tmp_path / "other"))
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            should_accelerate("KMeans", True)
        assert jax.config.jax_compilation_cache_dir == before

    def test_use_checkout_cache_prefers_env_then_fixed_dir(
            self, tmp_path, jax_cache_restore, monkeypatch):
        import jax

        from oap_mllib_tpu.config import get_config

        fixed = str(tmp_path / "checkout" / ".jax_cache")
        monkeypatch.setattr(progcache, "_persist_applied", None)
        monkeypatch.delenv(progcache.CACHE_ENV, raising=False)
        assert progcache.use_checkout_cache(fixed) == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert get_config().compilation_cache_dir == fixed
        env_dir = str(tmp_path / "env-cache")
        monkeypatch.setenv(progcache.CACHE_ENV, env_dir)
        assert progcache.use_checkout_cache(fixed) == env_dir
        assert jax.config.jax_compilation_cache_dir == fixed  # not re-set
        assert get_config().compilation_cache_dir == env_dir

    def test_dispatch_wires_cache_dir(self, tmp_path, jax_cache_restore,
                                      monkeypatch):
        monkeypatch.delenv(progcache.CACHE_ENV, raising=False)
        self._dispatch_wires_cache_dir(tmp_path)

    def _dispatch_wires_cache_dir(self, tmp_path):
        import jax

        from oap_mllib_tpu.utils.dispatch import should_accelerate

        cache_dir = str(tmp_path / "xla-cache")
        os.makedirs(cache_dir, exist_ok=True)
        set_config(compilation_cache_dir=cache_dir)
        assert should_accelerate("KMeans", True)
        assert jax.config.jax_compilation_cache_dir == cache_dir

    def test_fresh_program_persists_to_disk(self, tmp_path, rng,
                                            jax_cache_restore, monkeypatch):
        """A fit with the cache dir set serializes its executables —
        the artifact a warm process reloads instead of recompiling."""
        from oap_mllib_tpu.models.kmeans import KMeans

        monkeypatch.delenv(progcache.CACHE_ENV, raising=False)

        cache_dir = str(tmp_path / "xla-cache")
        os.makedirs(cache_dir, exist_ok=True)
        set_config(compilation_cache_dir=cache_dir)
        # a shape no other test uses, so the backend compile (and hence
        # the disk write) actually happens in this test
        x = rng.normal(size=(173, 9)).astype(np.float32)
        KMeans(k=3, seed=8, init_mode="random", max_iter=2).fit(x)
        assert len(os.listdir(cache_dir)) > 0


class TestCrossFitReuse:
    """The acceptance contract: the 2nd-through-Nth fit of any size in a
    bucket pays zero XLA compiles, and bucketing never changes results
    beyond fp summation order."""

    def _sizes(self):
        from oap_mllib_tpu.parallel.mesh import get_mesh

        mesh = get_mesh()
        m0 = mesh.shape[mesh.axis_names[0]] * 256
        # two sizes whose EXACT pads differ (3*m0 vs 4*m0) but whose x2
        # bucket (4*m0) is shared
        return 2 * m0 + 404, 3 * m0 + 37

    def test_kmeans_second_size_reuses_program(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        n1, n2 = self._sizes()
        x = rng.normal(size=(n2, 4)).astype(np.float32)

        def fit(n):
            return KMeans(
                k=4, seed=6, init_mode="random", max_iter=3
            ).fit(x[:n])

        m1 = fit(n1)
        assert m1.summary.accelerated
        before = progcache.xla_compile_count()
        m2 = fit(n2)
        assert m2.summary.accelerated
        assert progcache.xla_compile_count() - before == 0
        assert m2.summary.progcache["misses"] == 0
        assert m2.summary.progcache["hits"] > 0

    def test_kmeans_extra_masked_row_identical(self, rng):
        """Fitting n vs n+1 rows (same data + one extra weight-0 row)
        lands in one bucket and yields identical centers — the padding
        contract, exercised through the real table layer."""
        import jax.numpy as jnp

        from oap_mllib_tpu.data.table import DenseTable
        from oap_mllib_tpu.ops import kmeans_ops
        from oap_mllib_tpu.parallel.mesh import get_mesh

        n1, _ = self._sizes()
        mesh = get_mesh()
        x = rng.normal(size=(n1 + 1, 5)).astype(np.float32)
        init = x[rng.choice(n1, 4, replace=False)]
        t1 = DenseTable.from_numpy(x[:n1], mesh)
        t2 = DenseTable.from_numpy(x, mesh)
        assert t1.n_padded == t2.n_padded  # same bucket -> same program
        w2 = np.asarray(t2.mask).copy()
        w2[n1] = 0.0  # mask the extra point out
        r1 = kmeans_ops.lloyd_run(
            t1.data, t1.mask, jnp.asarray(init), 5,
            jnp.asarray(1e-6, jnp.float32),
        )
        r2 = kmeans_ops.lloyd_run(
            t2.data, jnp.asarray(w2), jnp.asarray(init), 5,
            jnp.asarray(1e-6, jnp.float32),
        )
        np.testing.assert_allclose(
            np.asarray(r1[0]), np.asarray(r2[0]), atol=1e-6
        )
        assert int(r1[1]) == int(r2[1])

    def test_kmeans_bucketing_parity_on_vs_off(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        n1, _ = self._sizes()
        x = rng.normal(size=(n1, 4)).astype(np.float32)
        m_on = KMeans(k=4, seed=6, init_mode="random", max_iter=4).fit(x)
        set_config(shape_bucketing="off")
        m_off = KMeans(k=4, seed=6, init_mode="random", max_iter=4).fit(x)
        np.testing.assert_allclose(
            m_on.cluster_centers_, m_off.cluster_centers_, atol=1e-6
        )

    def test_pca_second_size_reuses_program(self, rng):
        from oap_mllib_tpu.models.pca import PCA

        n1, n2 = self._sizes()
        x = rng.normal(size=(n2, 6)).astype(np.float32)
        p1 = PCA(k=3).fit(x[:n1])
        assert p1.summary["accelerated"]
        before = progcache.xla_compile_count()
        p2 = PCA(k=3).fit(x)
        assert p2.summary["accelerated"]
        assert progcache.xla_compile_count() - before == 0
        assert p2.summary["progcache"]["misses"] == 0

    def test_pca_bucketing_parity_on_vs_off(self, rng):
        import jax.numpy as jnp

        from oap_mllib_tpu.data.table import DenseTable
        from oap_mllib_tpu.ops import pca_ops
        from oap_mllib_tpu.parallel.mesh import get_mesh

        n1, _ = self._sizes()
        mesh = get_mesh()
        x = rng.normal(size=(n1, 6)).astype(np.float32) + 3.0
        covs = []
        for mode in ("on", "off"):
            set_config(shape_bucketing=mode)
            t = DenseTable.from_numpy(x, mesh)
            cov, mean = pca_ops.covariance(
                t.data, t.mask, jnp.asarray(float(t.n_rows), jnp.float32)
            )
            covs.append((np.asarray(cov), np.asarray(mean)))
        np.testing.assert_allclose(covs[0][0], covs[1][0], atol=1e-5)
        np.testing.assert_allclose(covs[0][1], covs[1][1], atol=1e-6)

    def test_als_extra_zero_rating_reuses_and_matches(self, rng):
        """The ALS leg: one extra implicit rating of 0 (contributes
        exactly nothing: A-weight alpha*|0|, b only for r > 0) lands in
        the grouped layout's padding slack — same shapes, same program,
        identical factors."""
        from oap_mllib_tpu.models.als import ALS

        n_users, n_items = 30, 20
        users = np.repeat(np.arange(n_users), 10)
        items = np.concatenate(
            [(np.arange(10) + j) % n_items for j in range(n_users)]
        )
        ratings = (rng.random(len(users)) * 4 + 1).astype(np.float32)

        def fit(u, i, r):
            # num_user_blocks=1 pins the single-device grouped path (the
            # 8-rank block path's per-rank group maxima legitimately
            # shift with the edge distribution)
            return ALS(
                rank=4, max_iter=2, reg_param=0.1, alpha=10.0,
                implicit_prefs=True, seed=3, num_user_blocks=1,
            ).fit(u, i, r, n_users=n_users, n_items=n_items)

        m1 = fit(users, items, ratings)
        assert m1.summary["accelerated"]
        assert m1.summary["als_kernel"] == "grouped"
        before = progcache.xla_compile_count()
        m2 = fit(
            np.append(users, 0),
            np.append(items, 17),
            np.append(ratings, np.float32(0.0)),
        )
        assert progcache.xla_compile_count() - before == 0
        assert m2.summary["progcache"]["misses"] == 0
        np.testing.assert_allclose(
            m1.user_factors_, m2.user_factors_, atol=1e-7
        )
        np.testing.assert_allclose(
            m1.item_factors_, m2.item_factors_, atol=1e-7
        )


def _named(name, scale):
    """A fresh function object called ``name``: a new trace each time,
    the same program (and persistent-cache key) for the same ``scale``."""
    import jax.numpy as jnp

    def fn(a):
        return jnp.cos(a) * scale + jnp.sin(a)

    fn.__name__ = fn.__qualname__ = name
    return fn


def _ready_seconds():
    from oap_mllib_tpu import telemetry

    return telemetry.metrics.family_total(progcache.READY_SECONDS)


def _compiled_total():
    from oap_mllib_tpu import telemetry

    return telemetry.metrics.family_total(progcache.PROGRAMS_COMPILED)


class TestProgramLedger:
    """The listener keeps the name jax gives each program it makes ready
    (ISSUE 35): what a first launch paid, by name, and whether the
    persistent cache served it."""

    def test_names_a_program_and_what_its_first_launch_paid(self):
        import jax
        import jax.numpy as jnp

        x = jnp.arange(7.0)
        assert "ledger_probe_a" not in progcache.program_ledger()
        count, ready = progcache.xla_compile_count(), _ready_seconds()
        before = progcache.stats()
        jax.jit(_named("ledger_probe_a", 3.0))(x).block_until_ready()
        entry = progcache.program_ledger()["ledger_probe_a"]
        assert entry["launches_first_seen"] == 1
        assert min(entry["trace_s"], entry["lower_s"], entry["backend_s"]) > 0
        assert progcache.xla_compile_count() == count + 1
        # the fit-level view: this program, with all three parts
        made = progcache.delta(before)["programs"]
        assert set(made) == {"ledger_probe_a"}
        assert made["ledger_probe_a"]["seconds"] == pytest.approx(
            entry["trace_s"] + entry["lower_s"] + entry["backend_s"]
        )
        assert made["ledger_probe_a"]["compiled"] + made["ledger_probe_a"]["loaded"] == 1
        # the body's jnp calls are traced inside the program's own trace:
        # booked once, under the outer name, and the total agrees
        assert _ready_seconds() - ready == pytest.approx(
            made["ledger_probe_a"]["seconds"]
        )
        assert "programs" not in progcache.delta(progcache.stats())

    def test_a_load_from_the_persistent_cache_is_told_from_a_compile(
            self, tmp_path, jax_cache_restore, monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.delenv(progcache.CACHE_ENV, raising=False)
        monkeypatch.setattr(progcache, "_persist_applied", None)
        progcache.ensure_persistent_cache(str(tmp_path / "xla-cache"))
        x = jnp.arange(11.0)
        compiled = _compiled_total()
        before = progcache.stats()
        jax.jit(_named("ledger_probe_b", 5.0))(x).block_until_ready()
        first = progcache.delta(before)["programs"]["ledger_probe_b"]
        assert (first["compiled"], first["loaded"]) == (1, 0)
        assert _compiled_total() == compiled + 1
        entry = progcache.program_ledger()["ledger_probe_b"]
        assert not entry["from_persistent_cache"] and entry["load_s"] == 0.0
        # the same program through a fresh trace: served from the directory
        before = progcache.stats()
        count = progcache.xla_compile_count()
        jax.jit(_named("ledger_probe_b", 5.0))(x).block_until_ready()
        second = progcache.delta(before)["programs"]["ledger_probe_b"]
        assert (second["compiled"], second["loaded"]) == (0, 1)
        assert _compiled_total() == compiled + 1
        # xla_compile_count keeps its meaning: the backend event, load or not
        assert progcache.xla_compile_count() == count + 1
        entry = progcache.program_ledger()["ledger_probe_b"]
        assert entry["launches_first_seen"] == 2 and entry["compiled"] == 1
        assert entry["load_s"] > 0 and not entry["from_persistent_cache"]
        # as the next process sees it: a ledger that starts empty
        monkeypatch.setattr(progcache, "_READY_LOG", [])
        jax.jit(_named("ledger_probe_b", 5.0))(x).block_until_ready()
        entry = progcache.program_ledger()["ledger_probe_b"]
        assert entry["launches_first_seen"] == 1 and entry["compiled"] == 0
        assert entry["from_persistent_cache"]
        assert 0 < entry["load_s"] <= entry["backend_s"]

    def test_a_forced_recompile_is_counted_by_name(self):
        import jax
        import jax.numpy as jnp

        fn = jax.jit(_named("ledger_probe_c", 2.0))
        fn(jnp.arange(5.0)).block_until_ready()
        one = progcache.program_ledger()["ledger_probe_c"]
        fn(jnp.arange(5.0)).block_until_ready()  # jit's own cache: no event
        assert progcache.program_ledger()["ledger_probe_c"] == one
        fn(jnp.arange(6.0)).block_until_ready()  # another shape: made ready anew
        two = progcache.program_ledger()["ledger_probe_c"]
        assert two["launches_first_seen"] == one["launches_first_seen"] + 1 == 2
        assert two["backend_s"] > one["backend_s"] and two["trace_s"] > one["trace_s"]

    @pytest.mark.parametrize("kwargs", [{}, {"fun_name": None}, {"fun_name": ""}])
    def test_an_event_without_a_name_is_booked_under_a_question_mark(self, kwargs):
        from jax import monitoring

        def unnamed():
            return dict(progcache.program_ledger().get(
                "?", {"launches_first_seen": 0, "backend_s": 0.0}
            ))

        was, count = unnamed(), progcache.xla_compile_count()
        monitoring.record_event_duration_secs(
            progcache._BACKEND_COMPILE_EVENT, 0.25, **kwargs
        )
        now = unnamed()
        assert now["launches_first_seen"] == was["launches_first_seen"] + 1
        assert now["backend_s"] == pytest.approx(was["backend_s"] + 0.25)
        assert progcache.xla_compile_count() == count + 1

    @pytest.mark.parametrize("name,want", [
        ("jit(write_piece)", "write_piece"), ("write_piece", "write_piece"),
        ("jit(<lambda>)", "<lambda>"), ("pmap(step)", "step"),
        (None, "?"), ("jit()", "?"),
    ])
    def test_one_name_for_the_three_events_of_a_program(self, name, want):
        assert progcache._program_name(name) == want

    def test_a_listener_never_raises_into_the_compile_path(self):
        from jax import monitoring

        assert progcache._never_raise(lambda *a, **k: 1 / 0)("event", 1.0) is None
        # a duration that is no number at all, through the real listeners
        monitoring.record_event_duration_secs(
            progcache._BACKEND_COMPILE_EVENT, "soon", fun_name="jit(x)"
        )
        # an inner trace's end with no start seen: clamped, not negative
        monitoring.record_event_duration_secs(
            progcache._TRACE_EVENT, 0.0, fun_name="ledger_probe_orphan"
        )
        assert getattr(progcache._pending, "tracing", 0) == 0

    def test_report_names_the_programs_a_fit_made_ready(self, rng):
        from oap_mllib_tpu import telemetry
        from oap_mllib_tpu.models.kmeans import KMeans

        # a shape no other test uses: its programs are made ready here
        x = rng.normal(size=(211, 11)).astype(np.float32)
        first = KMeans(k=3, seed=8, init_mode="random", max_iter=2).fit(x)
        programs = first.summary.progcache["programs"]
        assert first.summary.progcache["misses"] > 0 and programs
        text = telemetry.report(first.summary)
        costliest = max(programs, key=lambda n: programs[n]["seconds"])
        assert f"    {costliest}: " in text and "compiled" in text
        second = KMeans(k=3, seed=8, init_mode="random", max_iter=2).fit(x)
        assert "programs" not in second.summary.progcache
        assert "compiled," not in telemetry.report(second.summary)
