"""Resilience subsystem tests (utils/resilience.py, utils/faults.py).

Covers the tentpole contracts: the transient-error classifier, the
deterministic RetryPolicy, fault-registry determinism, each rung of the
degradation ladder (transient retry -> halved-chunk OOM retry -> CPU
fallback -> ResilienceError with history), the streamed numerical
guardrails, the bootstrap hardening, and fallback-vs-accelerated result
parity under ``device=cpu``.
"""

import time

import numpy as np
import pytest

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.data.stream import ChunkSource
from oap_mllib_tpu.utils import faults, resilience
from oap_mllib_tpu.utils.resilience import (
    NONFINITE,
    OOM,
    OOM_HOST,
    TRANSIENT,
    NonFiniteError,
    ResilienceError,
    ResilienceStats,
    RetryPolicy,
    classify_fault,
    halvings_available,
)


@pytest.fixture(autouse=True)
def _fast_retries():
    """Keep injected-fault tests snappy: near-zero backoff (the schedule
    logic is exercised either way), and a re-armed registry per test."""
    set_config(retry_backoff=0.001, retry_deadline=10.0)
    yield
    set_config(fault_spec="")
    faults.reset()


def _blobs(rng, n=600, d=6):
    proto = rng.normal(size=(3, d)).astype(np.float32) * 4.0
    return (proto[rng.integers(3, size=n)]
            + rng.normal(size=(n, d)).astype(np.float32) * 0.2)


class TestClassifier:
    def test_os_and_connection_errors_are_transient(self):
        assert classify_fault(OSError("disk hiccup")) == TRANSIENT
        assert classify_fault(ConnectionRefusedError("nope")) == TRANSIENT
        assert classify_fault(TimeoutError("slow")) == TRANSIENT
        assert classify_fault(RuntimeError("UNAVAILABLE: backend")) == TRANSIENT

    def test_oom_shapes(self):
        # the jaxlib XlaRuntimeError carries its status in the message —
        # the classifier must key on RESOURCE_EXHAUSTED textually
        assert classify_fault(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating")
        ) == OOM
        assert classify_fault(
            RuntimeError("failed to allocate 16.00G")
        ) == OOM

    def test_host_oom_is_distinct_from_device_oom(self):
        """A bare MemoryError (a failed np allocation) is the HOST
        class — the spill rung — while device markers stay OOM (the
        halved-chunk rung); a MemoryError CARRYING a device marker is
        still device (jaxlib raises MemoryError subclasses for XLA
        RESOURCE_EXHAUSTED)."""
        assert classify_fault(MemoryError("host")) == OOM_HOST
        assert classify_fault(
            MemoryError("RESOURCE_EXHAUSTED: out of memory")
        ) == OOM

    def test_non_faults_are_none(self):
        assert classify_fault(ValueError("bad k")) is None
        assert classify_fault(TypeError("wrong arg")) is None
        assert classify_fault(KeyError("x")) is None

    def test_injected_faults_carry_their_kind(self):
        assert classify_fault(
            faults.InjectedTransientError("x")) == TRANSIENT
        assert classify_fault(faults.InjectedOOMError("x")) == OOM
        assert classify_fault(faults.InjectedHostOOMError("x")) == OOM_HOST
        assert classify_fault(faults.InjectedPermanentError("x")) is None

    def test_nonfinite(self):
        assert classify_fault(NonFiniteError("NaN centroids")) == NONFINITE


class TestRetryPolicy:
    def test_backoff_grows_and_caps(self):
        p = RetryPolicy(backoff_s=0.1, multiplier=2.0, max_backoff_s=0.5,
                        jitter=0.0)
        delays = [p.delay_s(i) for i in range(5)]
        assert delays[0] == pytest.approx(0.1)
        assert delays[1] == pytest.approx(0.2)
        assert delays[2] == pytest.approx(0.4)
        assert delays[3] == pytest.approx(0.5)  # capped
        assert delays == sorted(delays)

    def test_jitter_is_deterministic_and_site_dependent(self):
        p = RetryPolicy(backoff_s=0.1, jitter=0.5)
        a = p.delay_s(1, "stream.read")
        assert a == p.delay_s(1, "stream.read")  # reproducible
        assert a != p.delay_s(1, "fit.execute")  # de-synchronized
        base = RetryPolicy(backoff_s=0.1, jitter=0.0).delay_s(1)
        assert base <= a <= base * 1.5

    def test_run_with_retry_counts_and_gives_up(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        stats = ResilienceStats()
        out = resilience.run_with_retry(
            flaky, policy=RetryPolicy(backoff_s=0.001), stats=stats,
            site="t",
        )
        assert out == "ok" and stats.retries == 2 and stats.faults == 2

        stats = ResilienceStats()
        with pytest.raises(OSError):
            resilience.run_with_retry(
                lambda: (_ for _ in ()).throw(OSError("always")),
                policy=RetryPolicy(max_retries=2, backoff_s=0.001),
                stats=stats, site="t",
            )
        assert stats.retries == 2  # exhausted, then re-raised

    def test_run_with_retry_never_retries_non_faults(self):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("API misuse")

        with pytest.raises(ValueError):
            resilience.run_with_retry(bad, site="t")
        assert len(calls) == 1

    def test_deadline_bounds_wall(self):
        t0 = time.monotonic()
        with pytest.raises(OSError):
            resilience.run_with_retry(
                lambda: (_ for _ in ()).throw(OSError("always")),
                policy=RetryPolicy(
                    max_retries=100, backoff_s=0.2, deadline_s=0.3
                ),
                site="t",
            )
        assert time.monotonic() - t0 < 2.0


class TestFaultRegistry:
    def test_grammar_and_determinism(self):
        set_config(fault_spec="stream.read:fail=2")
        fired = []
        for i in range(5):
            try:
                faults.maybe_fault("stream.read")
                fired.append(False)
            except faults.InjectedTransientError:
                fired.append(True)
        # exactly the FIRST TWO calls fault — deterministic by call index
        assert fired == [True, True, False, False, False]
        st = faults.stats()["stream.read"]
        assert st["fired"] == 2 and st["calls"] == 5 and st["limit"] == 2

    def test_reset_restarts_counters(self):
        set_config(fault_spec="prefetch.stage:fail=1")
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("prefetch.stage")
        faults.maybe_fault("prefetch.stage")  # budget spent
        faults.reset()
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("prefetch.stage")  # budget restored

    def test_unarmed_sites_never_fire(self):
        set_config(fault_spec="stream.read:fail=99")
        faults.maybe_fault("fit.execute")
        faults.maybe_fault("prefetch.stage")

    def test_persistent_and_oom_kinds(self):
        set_config(fault_spec="fit.execute:oom=*")
        for _ in range(3):
            with pytest.raises(faults.InjectedOOMError, match="RESOURCE"):
                faults.maybe_fault("fit.execute")

    def test_spec_change_rearms(self):
        set_config(fault_spec="stream.read:fail=1")
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("stream.read")
        set_config(fault_spec="")
        faults.maybe_fault("stream.read")  # disarmed by config change


class TestCheckpointFaultSites:
    """The ``ckpt.*`` fault sites (ISSUE 8 satellite): registry-level
    behavior here; the fit-level tiers (warn-never-kill writes, the
    corrupt-restore `resume` decision) are tests/test_checkpoint.py."""

    def test_sites_registered_and_grammar_accepts(self):
        assert "ckpt.write" in faults.SITES
        assert "ckpt.restore" in faults.SITES
        parsed = faults.parse_spec(
            "ckpt.write:fail=2,ckpt.restore:err=*"
        )
        assert parsed["ckpt.write"].limit == 2
        assert parsed["ckpt.restore"].limit == -1

    def test_all_kinds_fire_deterministically(self):
        for kind, exc in (
            ("fail", faults.InjectedTransientError),
            ("oom", faults.InjectedOOMError),
            ("err", faults.InjectedPermanentError),
            ("nan", faults.InjectedNonFiniteError),
        ):
            set_config(fault_spec=f"ckpt.write:{kind}=1")
            faults.reset()
            with pytest.raises(exc):
                faults.maybe_fault("ckpt.write")
            faults.maybe_fault("ckpt.write")  # budget spent: silent
            assert faults.stats()["ckpt.write"]["fired"] == 1

    def test_write_site_fault_never_escalates_the_ladder(self, rng):
        """A persistent ckpt.write fault must not consume ladder rungs:
        the fit completes accelerated with zero retries/degradations
        (checkpoint writes are insurance, outside the fault ladder)."""
        import tempfile

        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(
            checkpoint_dir=tempfile.mkdtemp(),
            fault_spec="ckpt.write:fail=*",
        )
        faults.reset()
        x = rng.normal(size=(600, 6)).astype(np.float32)
        m = KMeans(k=3, seed=1, max_iter=3).fit(
            ChunkSource.from_array(x, chunk_rows=256)
        )
        assert m.summary.accelerated
        assert m.summary.resilience["retries"] == 0
        assert m.summary.resilience["degradations"] == 0
        assert m.summary.checkpoint["writes"] == 0
        set_config(checkpoint_dir="")


class TestLadderVisibility:
    def test_stats_default_and_bypass_label(self):
        stats = ResilienceStats()
        assert stats.as_dict()["ladder"] == "active"
        out = resilience.resilient_fit(
            "t", lambda degraded: "ok", None, stats=stats
        )
        assert out == "ok"
        assert stats.ladder == "active"  # single-process world

    def test_bypass_label_when_world_large(self, monkeypatch):
        monkeypatch.setattr(resilience, "_world", lambda: 2)
        stats = ResilienceStats()
        resilience.resilient_fit(
            "t", lambda degraded: "ok", None, stats=stats
        )
        assert stats.ladder == "bypassed(static-world)"


class TestLadderRungs:
    """Each rung driven end to end through a real streamed K-Means fit."""

    def _fit(self, rng, **kw):
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _blobs(rng)
        src = ChunkSource.from_array(x, chunk_rows=128)
        return KMeans(k=3, seed=7, max_iter=8, **kw).fit(src)

    def test_transient_faults_absorbed_with_parity(self, rng):
        baseline = self._fit(rng)
        set_config(fault_spec="stream.read:fail=2,prefetch.stage:fail=1")
        faults.reset()
        m = self._fit(np.random.default_rng(42))
        res = m.summary.resilience
        assert res["retries"] == 3 and res["faults"] == 3
        assert res["degradations"] == 0
        assert m.summary.accelerated
        np.testing.assert_allclose(
            m.cluster_centers_, baseline.cluster_centers_, atol=1e-6
        )
        np.testing.assert_allclose(
            m.summary.training_cost, baseline.summary.training_cost,
            rtol=1e-6,
        )

    def test_oom_steps_to_halved_chunks_then_succeeds(self, rng):
        baseline = self._fit(rng)
        # exactly one OOM: the degraded (halved-chunk) retry completes
        set_config(fault_spec="fit.execute:oom=1")
        faults.reset()
        m = self._fit(np.random.default_rng(42))
        res = m.summary.resilience
        assert res["degradations"] == 1 and res["retries"] == 0
        assert m.summary.accelerated  # the DEGRADED rung, not fallback
        # halved chunks only re-block the passes: the centres, which are
        # ratios of well-conditioned sums, agree to float32 rounding
        c = baseline.cluster_centers_
        np.testing.assert_allclose(m.cluster_centers_, c, atol=1e-6)
        # the cost does NOT agree to 1e-5 relative, and need not: each
        # row's term is |x|^2 + |c|^2 - 2 x.c in float32, a difference
        # of numbers ~100 times the distance it leaves (blobs at radius
        # ~10, spread 0.2), so a row carries an absolute rounding error
        # of up to one float32 step of |x|^2 + |c|^2 whatever the
        # distance is, and re-blocking the pass re-rounds every row.
        # The bound is that step summed over the rows (7e-5 of this
        # cost; 1.1e-5 is what this CPU shows — ROADMAP D12)
        x = _blobs(np.random.default_rng(42)).astype(np.float64)
        near = c[((x[:, None, :] - c[None]) ** 2).sum(-1).argmin(1)]
        magnitude = (x ** 2).sum() + (near.astype(np.float64) ** 2).sum()
        assert abs(
            m.summary.training_cost - baseline.summary.training_cost
        ) <= np.finfo(np.float32).eps * magnitude

    def test_persistent_oom_escalates_to_fallback(self, rng):
        set_config(fault_spec="fit.execute:oom=*", fallback=True)
        faults.reset()
        m = self._fit(rng)  # no user-visible exception
        assert not m.summary.accelerated  # CPU reference path ran
        res = m.summary.resilience
        assert res["degradations"] == 2  # halved-chunk rung + CPU rung
        assert len(res["history"]) == 2

    def test_fallback_disabled_raises_with_history(self, rng):
        set_config(fault_spec="fit.execute:oom=*", fallback=False)
        faults.reset()
        with pytest.raises(ResilienceError, match="fault history"):
            self._fit(rng)

    def test_permanent_injected_fault_propagates_unmasked(self, rng):
        set_config(fault_spec="stream.read:err=1")
        faults.reset()
        with pytest.raises(faults.InjectedPermanentError):
            self._fit(rng)

    def test_streamed_pca_absorbs_transients(self, rng):
        from oap_mllib_tpu.models.pca import PCA

        x = _blobs(rng)
        baseline = PCA(k=2).fit(ChunkSource.from_array(x, chunk_rows=128))
        set_config(fault_spec="stream.read:fail=1,prefetch.stage:fail=1")
        faults.reset()
        m = PCA(k=2).fit(ChunkSource.from_array(x, chunk_rows=128))
        assert m.summary["resilience"]["retries"] == 2
        np.testing.assert_allclose(
            m.explained_variance_, baseline.explained_variance_, atol=1e-6
        )
        np.testing.assert_allclose(
            np.abs(m.components_), np.abs(baseline.components_), atol=1e-6
        )

    def test_streamed_als_absorbs_transients(self, rng):
        from oap_mllib_tpu.models.als import ALS

        u = rng.integers(30, size=400).astype(np.float64)
        i = rng.integers(20, size=400).astype(np.float64)
        r = rng.random(400)
        tri = np.stack([u, i, r], axis=1)

        def fit():
            return ALS(rank=3, max_iter=2, seed=3).fit(
                ChunkSource.from_array(tri, chunk_rows=128)
            )

        baseline = fit()
        set_config(fault_spec="stream.read:fail=2,prefetch.stage:fail=1")
        faults.reset()
        m = fit()
        assert m.summary["resilience"]["retries"] == 3
        assert m.summary["accelerated"]
        np.testing.assert_allclose(
            m.user_factors_, baseline.user_factors_, atol=1e-6
        )
        np.testing.assert_allclose(
            m.item_factors_, baseline.item_factors_, atol=1e-6
        )

    def test_geometric_halving_walks_to_the_floor(self, rng):
        """chunk_rows=256 has TWO halvings above the 64-row floor
        (256 -> 128 -> 64): a persistent device OOM steps both, records
        the divisor trail in ``halvings``, then takes the CPU rung —
        the geometric generalization of the old single halved retry."""
        from oap_mllib_tpu.models.kmeans import KMeans

        assert halvings_available(256) == 2
        assert halvings_available(128) == 1
        assert halvings_available(64) == 1  # legacy single rung floor
        set_config(fault_spec="fit.execute:oom=*", fallback=True)
        faults.reset()
        x = _blobs(rng)
        m = KMeans(k=3, seed=7, max_iter=8).fit(
            ChunkSource.from_array(x, chunk_rows=256)
        )
        res = m.summary.resilience
        assert not m.summary.accelerated
        assert res["degradations"] == 3  # 2 halvings + the CPU rung
        assert res["halvings"] == [2, 4]
        assert len(res["history"]) == 3

    def test_halvings_bounded_by_retry_limit(self, rng):
        """retry_limit caps the geometric walk even with chunk headroom
        left (a fit must not halve forever on a huge chunk)."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(
            fault_spec="fit.execute:oom=*", fallback=True, retry_limit=1
        )
        faults.reset()
        x = _blobs(rng)
        m = KMeans(k=3, seed=7, max_iter=4).fit(
            ChunkSource.from_array(x, chunk_rows=512)
        )
        res = m.summary.resilience
        assert res["halvings"] == [2]  # one rung despite 3 of headroom
        assert res["degradations"] == 2
        set_config(retry_limit=5)

    def test_host_oom_spills_to_disk_and_completes(self, rng):
        """The spill rung: a host-classified OOM mid-pass stages the
        memory-backed source to a disk spill and the fit completes
        ACCELERATED through the streamed route, bit-identical to the
        clean run (the spill preserves rows, order, and chunking)."""
        from oap_mllib_tpu.models.kmeans import KMeans

        baseline = self._fit(rng)
        set_config(fault_spec="prefetch.stage:oomhost=1")
        faults.reset()
        m = self._fit(np.random.default_rng(42))
        res = m.summary.resilience
        assert res["spilled"] is True
        assert res["degradations"] == 1  # the spill rung only
        assert res["halvings"] == []
        assert m.summary.accelerated
        assert m.summary.route["spilled"] is True
        np.testing.assert_allclose(
            m.cluster_centers_, baseline.cluster_centers_, atol=1e-6
        )

    def test_failed_spill_falls_through_never_corrupts(self, rng, tmp_path):
        """A spill whose writes fault falls through the ladder (here to
        the halving rung, which absorbs the one-shot host OOM) — and
        the spill dir holds no committed spill, only ignorable tmp."""
        import os

        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(
            spill_dir=str(tmp_path),
            fault_spec="prefetch.stage:oomhost=1,spill.write:fail=*",
        )
        faults.reset()
        m = self._fit(rng)
        res = m.summary.resilience
        assert res["spilled"] is False  # the rung fired but failed
        assert m.summary.accelerated  # halving rung absorbed it
        committed = [
            f for f in os.listdir(tmp_path) if not f.endswith(".tmp")
            and os.path.getsize(os.path.join(tmp_path, f)) > 0
        ]
        assert committed == []
        set_config(spill_dir="")

    def test_disk_backed_sources_do_not_spill(self, rng, tmp_path):
        """A source already on disk has nothing to spill: a host OOM
        falls straight through to the halving rung."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _blobs(rng)
        path = str(tmp_path / "x.npy")
        np.save(path, x)
        set_config(fault_spec="prefetch.stage:oomhost=1")
        faults.reset()
        m = KMeans(k=3, seed=7, max_iter=8).fit(
            ChunkSource.from_npy(path, chunk_rows=128)
        )
        res = m.summary.resilience
        assert res["spilled"] is False
        assert res["halvings"] == [2]
        assert m.summary.accelerated

    def test_als_degraded_rung_matches(self, rng):
        """One OOM routes the ALS fit to the streamed kernels at halved
        blocks; factors must match the clean grouped fit (chunked
        segment-sums only reorder additions)."""
        from oap_mllib_tpu.models.als import ALS

        u = rng.integers(30, size=400)
        i = rng.integers(20, size=400)
        r = rng.random(400).astype(np.float32)
        baseline = ALS(rank=3, max_iter=2, seed=3).fit(u, i, r)
        set_config(fault_spec="fit.execute:oom=1")
        faults.reset()
        m = ALS(rank=3, max_iter=2, seed=3).fit(u, i, r)
        assert m.summary["resilience"]["degradations"] == 1
        assert m.summary["accelerated"]
        np.testing.assert_allclose(
            m.user_factors_, baseline.user_factors_, atol=2e-5, rtol=2e-5
        )


class TestNumericalGuardrails:
    def test_kmeans_nan_data_raises_by_default(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _blobs(rng, n=256)
        x[7, 2] = np.nan
        src = ChunkSource.from_array(x, chunk_rows=64)
        with pytest.raises(NonFiniteError, match="centroids"):
            KMeans(k=3, seed=1, max_iter=3, init_mode="random").fit(src)

    def test_pca_overflow_gram_detected(self, rng):
        """f32 Gram overflow (x ~ 3e19 squares past f32 max) must trip
        the Gram-pass guardrail, not silently produce Inf components."""
        from oap_mllib_tpu.models.pca import PCA

        x = (rng.normal(size=(256, 4)) * 3e19).astype(np.float32)
        src = ChunkSource.from_array(x, chunk_rows=64)
        with pytest.raises(NonFiniteError, match="Gram"):
            PCA(k=2).fit(src)

    def test_pca_overflow_falls_back_when_configured(self, rng):
        """nonfinite_policy="fallback": the same overflow degrades to the
        f64 NumPy path, which handles the magnitudes fine."""
        from oap_mllib_tpu.models.pca import PCA

        set_config(nonfinite_policy="fallback")
        x = (rng.normal(size=(256, 4)) * 3e19).astype(np.float32)
        src = ChunkSource.from_array(x, chunk_rows=64)
        m = PCA(k=2).fit(src)
        assert not m.summary["accelerated"]
        assert np.all(np.isfinite(m.components_))
        assert m.summary["resilience"]["degradations"] == 1

    def test_nonfinite_raise_beats_fallback_config(self, rng):
        """policy="raise" surfaces the NonFiniteError even when
        Config.fallback would allow degrading — masking NaNs behind a
        CPU rerun is exactly what the knob exists to prevent."""
        from oap_mllib_tpu.models.kmeans import KMeans

        set_config(nonfinite_policy="raise", fallback=True)
        x = _blobs(rng, n=256)
        x[3, 0] = np.inf
        src = ChunkSource.from_array(x, chunk_rows=64)
        with pytest.raises(NonFiniteError):
            KMeans(k=3, seed=1, max_iter=3, init_mode="random").fit(src)


class TestBootstrapHardening:
    def test_nonzero_rank_error_names_env_seen(self, monkeypatch):
        from oap_mllib_tpu.parallel import bootstrap

        monkeypatch.delenv(
            "OAP_MLLIB_TPU_COORDINATOR_ADDRESS", raising=False
        )
        set_config(num_processes=2, process_id=1, coordinator_address="")
        with pytest.raises(ValueError) as ei:
            bootstrap.initialize_distributed()
        msg = str(ei.value)
        assert "OAP_MLLIB_TPU_COORDINATOR_ADDRESS=None" in msg
        assert "process_id=1" in msg and "num_processes=2" in msg

    def test_connect_retries_under_budget(self, monkeypatch):
        """bootstrap.connect transient faults retry with backoff; the
        stubbed initialize then succeeds on the third attempt."""
        import jax

        from oap_mllib_tpu.parallel import bootstrap

        calls = []
        monkeypatch.setattr(
            jax.distributed, "initialize",
            lambda **kw: calls.append(kw),
        )
        monkeypatch.setattr(bootstrap, "_initialized", False)
        set_config(
            fault_spec="bootstrap.connect:fail=2", bootstrap_timeout=30.0
        )
        faults.reset()
        assert bootstrap.initialize_distributed(
            "127.0.0.1:9999", num_processes=2, process_id=0
        )
        assert len(calls) == 1  # two faulted attempts never reached jax
        monkeypatch.setattr(bootstrap, "_initialized", False)

    def test_connect_timeout_names_coordinator_rank_elapsed(
        self, monkeypatch
    ):
        from oap_mllib_tpu.parallel import bootstrap

        monkeypatch.setattr(bootstrap, "_initialized", False)
        set_config(
            fault_spec="bootstrap.connect:fail=*", bootstrap_timeout=0.05
        )
        faults.reset()
        with pytest.raises(RuntimeError) as ei:
            bootstrap.initialize_distributed(
                "10.9.9.9:321", num_processes=4, process_id=2
            )
        msg = str(ei.value)
        assert "10.9.9.9:321" in msg
        assert "rank=2/4" in msg
        assert "bootstrap_timeout" in msg

    def test_free_port_returns_bindable_port(self):
        import socket

        from oap_mllib_tpu.parallel.bootstrap import free_port

        p = free_port("127.0.0.1", 23000)
        assert p >= 23000
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind(("127.0.0.1", p))
        finally:
            s.close()


class TestFallbackParity:
    """device=cpu forces the NumPy reference path; its results must
    agree with the accelerated (XLA-on-CPU) path on small fixtures —
    the contract that makes the ladder's final rung a safe landing."""

    def test_kmeans_cost_parity(self, rng):
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _blobs(rng)
        acc = KMeans(k=3, seed=7, max_iter=25).fit(x)
        assert acc.summary.accelerated
        set_config(device="cpu")
        fb = KMeans(k=3, seed=7, max_iter=25).fit(x)
        assert not fb.summary.accelerated
        # different init RNG streams, same well-separated optimum
        np.testing.assert_allclose(
            fb.summary.training_cost, acc.summary.training_cost, rtol=1e-3
        )

    def test_pca_parity(self, rng):
        from oap_mllib_tpu.models.pca import PCA

        x = rng.normal(size=(400, 8)).astype(np.float32) @ np.diag(
            [5, 4, 3, 2, 1, 0.5, 0.2, 0.1]
        ).astype(np.float32)
        acc = PCA(k=3).fit(x)
        assert acc.summary["accelerated"]
        set_config(device="cpu")
        fb = PCA(k=3).fit(x)
        assert not fb.summary["accelerated"]
        np.testing.assert_allclose(
            fb.explained_variance_, acc.explained_variance_, atol=1e-4
        )
        np.testing.assert_allclose(
            np.abs(fb.components_), np.abs(acc.components_), atol=1e-3
        )

    def test_als_factor_parity_with_shared_init(self, rng):
        from oap_mllib_tpu.fallback import als_np
        from oap_mllib_tpu.models.als import ALS

        nu, ni, rank = 25, 18, 3
        u = rng.integers(nu, size=500)
        i = rng.integers(ni, size=500)
        u[0], i[0] = nu - 1, ni - 1
        r = rng.random(500).astype(np.float32) * 4 + 1
        init = (
            als_np.init_factors(nu, rank, 3),
            als_np.init_factors(ni, rank, 4),
        )
        acc = ALS(rank=rank, max_iter=3, seed=3).fit(u, i, r, init=init)
        assert acc.summary["accelerated"]
        set_config(device="cpu")
        fb = ALS(rank=rank, max_iter=3, seed=3).fit(u, i, r, init=init)
        assert not fb.summary["accelerated"]
        np.testing.assert_allclose(
            fb.user_factors_, acc.user_factors_, atol=2e-3, rtol=2e-3
        )
        np.testing.assert_allclose(
            fb.item_factors_, acc.item_factors_, atol=2e-3, rtol=2e-3
        )


class TestStatsSurface:
    def test_summaries_carry_resilience_next_to_progcache(self, rng):
        """Every accelerated fit summary reports the resilience counters
        beside the progcache delta — the observability contract."""
        from oap_mllib_tpu.models.kmeans import KMeans
        from oap_mllib_tpu.models.pca import PCA

        x = _blobs(rng, n=300)
        km = KMeans(k=3, seed=1, max_iter=3).fit(x)
        assert hasattr(km.summary, "progcache")
        assert km.summary.resilience["faults"] == 0
        pc = PCA(k=2).fit(x)
        assert "progcache" in pc.summary and "resilience" in pc.summary

    def test_merge_stats_handles_both_summary_shapes(self):
        stats = ResilienceStats()
        stats.retries = 2
        d = {}
        resilience.merge_stats(d, stats)
        assert d["resilience"]["retries"] == 2

        class S:
            pass

        s = S()
        resilience.merge_stats(s, stats)
        assert s.resilience["retries"] == 2
