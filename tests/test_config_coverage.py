"""Config-surface coverage: every field is read somewhere, documented,
and env-overridable — what the round-3 audit asked for (the round-3
`Config.seed` was documented but read by nothing)."""

import dataclasses
import os
import re

import numpy as np
import pytest

from oap_mllib_tpu.config import Config, set_config

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(HERE, "..", "oap_mllib_tpu")
DOCS = os.path.join(HERE, "..", "docs", "configuration.md")


def _package_source_without_config():
    parts = []
    for root, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py") and f != "config.py":
                with open(os.path.join(root, f)) as fh:
                    parts.append(fh.read())
    return "\n".join(parts)


class TestConfigCoverage:
    def test_every_field_is_read_somewhere(self):
        """A Config field nothing reads is dead weight that will drift
        from its docs (the round-3 seed bug).  Accepted read patterns:
        ``cfg.NAME`` / ``conf.NAME`` / ``config.NAME`` /
        ``get_config().NAME``."""
        src = _package_source_without_config()
        for f in dataclasses.fields(Config):
            pat = rf"(cfg|conf|config|get_config\(\))\.{f.name}\b"
            assert re.search(pat, src), (
                f"Config.{f.name} is read nowhere in the package — wire it "
                "or delete it (and its docs row)"
            )

    def test_every_field_is_documented(self):
        """docs/configuration.md's field table and the dataclass must
        list the same fields, both directions."""
        with open(DOCS) as fh:
            doc = fh.read()
        fields = {f.name for f in dataclasses.fields(Config)}
        documented = set(re.findall(r"^\| `(\w+)` \|", doc, re.M))
        assert fields - documented == set(), "undocumented Config fields"
        assert documented - fields == set(), "docs rows for deleted fields"

    def test_env_override_every_field(self, monkeypatch):
        """OAP_MLLIB_TPU_<FIELD> overrides each field with the right
        type coercion."""
        types = {"bool": bool, "int": int, "float": float, "str": str}
        samples = {bool: "true", int: "7", float: "2.5", str: "xyz"}
        for f in dataclasses.fields(Config):
            t = types.get(str(f.type), str)
            monkeypatch.setenv(
                "OAP_MLLIB_TPU_" + f.name.upper(), samples[t]
            )
        cfg = Config.from_env()
        for f in dataclasses.fields(Config):
            t = types.get(str(f.type), str)
            expected = {bool: True, int: 7, float: 2.5, str: "xyz"}[t]
            assert getattr(cfg, f.name) == expected, f.name

    def test_seed_default_flows_to_estimators(self):
        """Config.seed is the default RNG seed for estimators that do
        not set one (docs/configuration.md row); an explicit seed wins."""
        from oap_mllib_tpu.models.als import ALS
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(seed=123)
        assert KMeans().seed == 123
        assert ALS().seed == 123
        assert KMeans(seed=5).seed == 5
        assert ALS(seed=5).seed == 5

    def test_seed_default_flows_through_compat_layers(self):
        """The drop-in surfaces must honor it too (the feature is
        advertised for exactly the unmodified-user-code path): compat
        builders and the pyspark adapters resolve an unset seed from
        config at fit time."""
        from oap_mllib_tpu.compat import spark as compat_spark
        from oap_mllib_tpu.compat import pyspark as compat_pyspark

        set_config(seed=77)
        assert compat_spark.KMeans().getSeed() == 77
        assert compat_spark.ALS().getSeed() == 77
        assert compat_pyspark.KMeans().getSeed() == 77
        assert compat_pyspark.ALS().getSeed() == 77
        assert compat_spark.KMeans().setSeed(9).getSeed() == 9
        assert compat_pyspark.ALS(seed=9).getSeed() == 9

    def test_seed_default_changes_random_init(self, rng):
        """The wired seed actually reaches the RNG: two config seeds give
        different random-init clusterings of ambiguous data."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = rng.normal(size=(200, 4)).astype(np.float32)
        set_config(seed=1)
        m1 = KMeans(k=8, init_mode="random", max_iter=0).fit(x)
        set_config(seed=2)
        m2 = KMeans(k=8, init_mode="random", max_iter=0).fit(x)
        set_config(seed=1)
        m3 = KMeans(k=8, init_mode="random", max_iter=0).fit(x)
        assert not np.allclose(m1.cluster_centers_, m2.cluster_centers_)
        np.testing.assert_allclose(m1.cluster_centers_, m3.cluster_centers_)

    def test_unknown_field_rejected(self):
        with pytest.raises(ValueError, match="unknown config field"):
            set_config(sead=1)

    def test_shape_bucketing_typo_raises_at_fit(self, rng):
        """The kmeans_kernel/als_kernel contract: a typo'd knob must
        raise, not silently disable compile amortization."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(shape_bucketing="bogus")
        x = rng.normal(size=(64, 4)).astype(np.float32)
        with pytest.raises(ValueError, match="shape_bucketing"):
            KMeans(k=2, init_mode="random", max_iter=1).fit(x)

    def test_shape_bucketing_accepted_values(self):
        from oap_mllib_tpu.data.bucketing import bucket_factor

        assert bucket_factor("on") == 2.0
        assert bucket_factor("x2") == 2.0
        assert bucket_factor("off") is None
        assert bucket_factor("1.5") == 1.5

    def test_fault_spec_typo_raises(self):
        """A typo'd fault_spec must raise naming the valid sites — a spec
        that silently injects nothing defeats the point of fault gates
        (the kmeans_kernel/als_kernel/shape_bucketing contract)."""
        from oap_mllib_tpu.utils import faults

        set_config(fault_spec="stream.reed:fail=2")
        with pytest.raises(ValueError, match="stream.read"):
            faults.maybe_fault("stream.read")
        set_config(fault_spec="stream.read:boom=2")
        with pytest.raises(ValueError, match="kind"):
            faults.maybe_fault("stream.read")
        set_config(fault_spec="garbage")
        with pytest.raises(ValueError, match="site:kind=count"):
            faults.maybe_fault("stream.read")

    def test_nonfinite_policy_typo_raises_at_fit(self, rng):
        """The same contract for nonfinite_policy: a typo raises at the
        first streamed guardrail, not silently behaving like 'raise'."""
        from oap_mllib_tpu.data.stream import ChunkSource
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(nonfinite_policy="bogus")
        x = rng.normal(size=(128, 4)).astype(np.float32)
        src = ChunkSource.from_array(x, chunk_rows=64)
        with pytest.raises(ValueError, match="nonfinite_policy"):
            KMeans(k=2, init_mode="random", max_iter=1).fit(src)

    def test_pca_kernel_typo_raises_at_fit(self, rng):
        """The kmeans_kernel contract for the PCA Gram kernel knob
        (ISSUE 9): a typo raises at fit entry, not silently keeping the
        XLA pass."""
        from oap_mllib_tpu.models.pca import PCA

        set_config(pca_kernel="bogus")
        x = rng.normal(size=(64, 4)).astype(np.float32)
        with pytest.raises(ValueError, match="pca_kernel"):
            PCA(k=2).fit(x)

    def test_als_solve_kernel_typo_raises_at_fit(self, rng):
        """Same contract for the ALS solve-kernel knob (ISSUE 9): the
        resolver runs at every runner entry."""
        from oap_mllib_tpu.models.als import ALS

        set_config(als_solve_kernel="bogus")
        u = rng.integers(0, 20, 100)
        i = rng.integers(0, 15, 100)
        r = (rng.random(100) * 4 + 1).astype(np.float32)
        with pytest.raises(ValueError, match="als_solve_kernel"):
            ALS(rank=4, max_iter=1).fit(u, i, r)

    def test_ring_reduction_typo_raises_at_fit(self, rng):
        """Same contract for the ring knob (ISSUE 9): validated on every
        accelerated K-Means dispatch, single-device included."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(ring_reduction="ring")
        x = rng.normal(size=(64, 4)).astype(np.float32)
        with pytest.raises(ValueError, match="ring_reduction"):
            KMeans(k=2, init_mode="random", max_iter=1).fit(x)

    def test_compute_precision_typo_raises_at_fit(self, rng):
        """The kmeans_kernel/als_kernel contract for the precision
        policy: a typo'd tier must raise at fit entry, not silently run
        f32."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(compute_precision="bf8")
        x = rng.normal(size=(64, 4)).astype(np.float32)
        with pytest.raises(ValueError, match="compute_precision"):
            KMeans(k=2, init_mode="random", max_iter=1).fit(x)

    def test_per_algo_precision_overrides_inherit_and_validate(self):
        from oap_mllib_tpu.utils import precision as psn

        set_config(compute_precision="tf32")
        # empty overrides inherit the global policy
        assert psn.resolve("kmeans").name == "tf32"
        assert psn.resolve("pca").name == "tf32"
        set_config(pca_precision="f32")
        assert psn.resolve("pca").name == "f32"
        assert psn.resolve("als").name == "tf32"
        set_config(kmeans_precision="nope")
        with pytest.raises(ValueError, match="kmeans_precision"):
            psn.resolve("kmeans")

    def test_collective_timeout_negative_raises_at_dispatch(self):
        """The kmeans_kernel/fault_spec contract for the recovery plane:
        a nonsense deadline raises at the dispatch seam, not silently
        disarming the watchdog (utils/recovery.py)."""
        from oap_mllib_tpu.utils import recovery

        set_config(collective_timeout=-1.0)
        with pytest.raises(ValueError, match="collective_timeout"):
            recovery.guarded_dispatch("psum", "data", lambda: 1)

    def test_chaos_typo_raises(self):
        """A malformed chaos spec must raise naming the grammar — a
        chaos drill that silently injects nothing proves nothing."""
        from oap_mllib_tpu.utils import faults

        set_config(chaos="garbage")
        with pytest.raises(ValueError, match="seed:rate"):
            faults.maybe_fault("stream.read")
        set_config(chaos="7:0.1:boom")
        with pytest.raises(ValueError, match="kind"):
            faults.maybe_fault("stream.read")

    def test_fleet_stats_typo_raises_at_pass(self, rng):
        """The kmeans_kernel contract for the fleet plane (ISSUE 11): a
        typo'd mode raises at the first streamed pass, not silently
        disarming the rollups."""
        from oap_mllib_tpu.data.stream import ChunkSource
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(fleet_stats="always")
        x = rng.normal(size=(200, 4)).astype(np.float32)

        def gen():
            for lo in range(0, 200, 100):
                yield x[lo:lo + 100]

        src = ChunkSource(gen, 4, 100, n_rows=200)
        with pytest.raises(ValueError, match="fleet_stats"):
            KMeans(k=2, init_mode="random", max_iter=1).fit(src)

    def test_metrics_port_negative_raises(self):
        from oap_mllib_tpu.telemetry import fleet

        set_config(metrics_port=-5)
        with pytest.raises(ValueError, match="metrics_port"):
            fleet.maybe_serve()

    def test_flight_recorder_negative_raises(self):
        from oap_mllib_tpu.telemetry import flightrec

        set_config(flight_recorder=-3)
        with pytest.raises(ValueError, match="flight_recorder"):
            flightrec.record("span_open", "x")

    def test_capability_sharding_typo_raises(self):
        """The kmeans_kernel contract for the balance plane (ISSUE 15):
        a typo'd mode raises at the armed() check, not silently keeping
        equal shards."""
        from oap_mllib_tpu.parallel import balance

        set_config(capability_sharding="weighted")
        with pytest.raises(ValueError, match="capability_sharding"):
            balance.armed(2)

    def test_rank_capability_typo_raises(self):
        from oap_mllib_tpu.utils import dispatch

        set_config(rank_capability="slow")
        with pytest.raises(ValueError, match="rank_capability"):
            dispatch.pinned_capability()
        set_config(rank_capability="-1.0")
        with pytest.raises(ValueError, match="> 0"):
            dispatch.pinned_capability()

    def test_rebalance_knobs_validate(self):
        from oap_mllib_tpu.parallel import balance

        set_config(rebalance_threshold=0.9)
        with pytest.raises(ValueError, match="rebalance_threshold"):
            balance.rebalance_threshold_cfg()
        set_config(rebalance_threshold=1.5, rebalance_patience=0)
        with pytest.raises(ValueError, match="rebalance_patience"):
            balance.rebalance_patience_cfg()

    def test_supervisor_knobs_reach_supervisor(self, tmp_path):
        """restart_budget / restart_backoff / shrink_after flow into
        Supervisor defaults (utils/supervisor.py)."""
        from oap_mllib_tpu.utils.supervisor import Supervisor

        set_config(restart_budget=9, restart_backoff=0.5, shrink_after=3)
        sup = Supervisor(lambda r, w, c, a: ["true"], 1,
                         str(tmp_path / "sb"))
        assert sup.restart_budget == 9
        assert sup.restart_backoff == 0.5
        assert sup.shrink_after == 3

    def test_crash_dir_arms_the_sideband(self, tmp_path):
        from oap_mllib_tpu.utils import recovery

        set_config(crash_dir="")
        assert recovery.write_crash_record("s", "oom", "x") is None
        set_config(crash_dir=str(tmp_path))
        path = recovery.write_crash_record("s", "oom", "x")
        assert path is not None and path.startswith(str(tmp_path))

    def test_memory_budget_typo_raises_at_fit(self, rng):
        """The kmeans_kernel contract for the route planner (ISSUE 12):
        a budget that parses to nothing must raise at fit entry, not
        silently plan unbounded."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(memory_budget_hbm="12Q")
        x = rng.normal(size=(64, 4)).astype(np.float32)
        with pytest.raises(ValueError, match="memory budget"):
            KMeans(k=2, init_mode="random", max_iter=1).fit(x)
        set_config(memory_budget_hbm="")

    def test_budget_knobs_reach_planner(self):
        from oap_mllib_tpu.utils import membudget

        set_config(memory_budget_hbm="64M", memory_budget_host="2G")
        b = membudget.Budgets.resolve()
        assert b.hbm == 64 << 20 and b.host == 2 << 30
        assert b.hbm_source == "config" and b.host_source == "config"
        set_config(memory_budget_hbm="", memory_budget_host="")

    def test_spill_dir_reaches_spill(self, rng, tmp_path):
        import os

        from oap_mllib_tpu.data.stream import ChunkSource

        set_config(spill_dir=str(tmp_path))
        x = rng.normal(size=(100, 3)).astype(np.float32)
        spilled = ChunkSource.from_array(x, chunk_rows=64).spill_to_disk()
        np.testing.assert_array_equal(spilled.to_array(), x)
        assert any(
            f.startswith("oap-spill.") for f in os.listdir(tmp_path)
        )
        set_config(spill_dir="")

    def test_retry_knobs_reach_policy(self):
        """retry_limit / retry_backoff / retry_deadline flow into
        RetryPolicy.from_config with float coercion intact."""
        from oap_mllib_tpu.utils.resilience import RetryPolicy

        set_config(retry_limit=2, retry_backoff=0.25, retry_deadline=9.0)
        p = RetryPolicy.from_config()
        assert p.max_retries == 2
        assert p.backoff_s == 0.25
        assert p.deadline_s == 9.0

    def test_profile_dir_respects_config_overrides(self, monkeypatch,
                                                   tmp_path):
        """Config.profile_dir (the promoted OAP_MLLIB_TPU_PROFILE_DIR)
        drives utils/profiling.maybe_trace through the config layer, so
        set_config/scoped overrides work — not just the raw env var."""
        from oap_mllib_tpu.utils import profiling

        traced = []

        @__import__("contextlib").contextmanager
        def fake_trace(log_dir):
            traced.append(log_dir)
            yield

        monkeypatch.setattr(profiling, "trace", fake_trace)
        with profiling.maybe_trace():
            pass
        assert traced == []  # default: off
        set_config(profile_dir=str(tmp_path))
        with profiling.maybe_trace():
            pass
        assert traced == [str(tmp_path)]

    def test_profile_dir_env_coerced(self, monkeypatch):
        """The env var now flows through the standard coercion like
        every other knob."""
        monkeypatch.setenv("OAP_MLLIB_TPU_PROFILE_DIR", "/tmp/x")
        assert Config.from_env().profile_dir == "/tmp/x"

    def test_telemetry_log_arms_the_jsonl_sink(self, tmp_path):
        from oap_mllib_tpu.telemetry.export import sink_path

        assert sink_path() is None  # default: off
        set_config(telemetry_log=str(tmp_path / "t.jsonl"))
        assert sink_path() == str(tmp_path / "t.jsonl")

    def test_compilation_cache_dir_wires_jax_config(self, tmp_path,
                                                    monkeypatch):
        """Config.compilation_cache_dir reaches jax's persistent cache
        at dispatch time (the every-fit chokepoint) — where the
        environment does not already own the cache."""
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

        from oap_mllib_tpu.utils import progcache
        from oap_mllib_tpu.utils.dispatch import should_accelerate

        prev_dir = jax.config.jax_compilation_cache_dir
        prev_applied = progcache._persist_applied
        try:
            cache_dir = str(tmp_path / "xla")
            set_config(compilation_cache_dir=cache_dir)
            should_accelerate("PCA", True)
            assert jax.config.jax_compilation_cache_dir == cache_dir
        finally:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            progcache._persist_applied = prev_applied
