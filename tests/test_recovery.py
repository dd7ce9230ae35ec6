"""Live-world recovery plane units (ISSUE 10, utils/recovery.py +
utils/faults.py chaos/kill): collective deadlines, the crash-record
sideband, coordinated abort, the chaos schedule, and the supervised
ladder stamp — everything the 2-process drills exercise end to end,
proven here with stubbed worlds so the logic is asserted even on hosts
that cannot form multiprocess jax worlds."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.utils import faults, recovery

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestConfigSurface:
    def test_negative_collective_timeout_raises(self):
        set_config(collective_timeout=-1.0)
        with pytest.raises(ValueError, match="collective_timeout"):
            recovery.collective_timeout_cfg()

    def test_negative_timeout_raises_at_dispatch_even_single_process(self):
        """The kmeans_kernel/fault_spec contract: a nonsense knob must
        raise at the seam, not silently disarm."""
        set_config(collective_timeout=-2.0)
        with pytest.raises(ValueError, match="collective_timeout"):
            recovery.guarded_dispatch("psum", "data", lambda: 1)

    def test_zero_is_disarmed_passthrough(self):
        set_config(collective_timeout=0.0)
        assert recovery.guarded_dispatch("psum", "data", lambda: 41) == 41

    def test_chaos_typo_raises_at_first_site_call(self):
        set_config(chaos="not-a-spec")
        with pytest.raises(ValueError, match="seed:rate"):
            faults.maybe_fault("stream.read")


class TestChaosSchedule:
    def test_parse_grammar(self):
        st = faults.parse_chaos("7:0.25:fail+kill:3")
        assert (st.seed, st.rate, st.kinds, st.budget) == (
            7, 0.25, ["fail", "kill"], 3
        )
        assert faults.parse_chaos("") is None
        assert faults.parse_chaos("5:0.5").kinds == ["fail"]
        assert faults.parse_chaos("5:0.5:oom:*").budget == -1

    @pytest.mark.parametrize("bad", [
        "x:0.1", "7:nope", "7:1.5", "7:-0.1", "7:0.1:boom",
        "7:0.1:fail:-1", "7", "7:0.1:fail:3:extra",
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            faults.parse_chaos(bad)

    def test_decision_is_deterministic_and_rank_dependent(self):
        st = faults.parse_chaos("11:0.5")
        seq0 = [st.decide("stream.read", c, 0) for c in range(64)]
        assert seq0 == [st.decide("stream.read", c, 0) for c in range(64)]
        seq1 = [st.decide("stream.read", c, 1) for c in range(64)]
        # ranks see INDEPENDENT schedules — the one-rank-killed,
        # peers-survive drill depends on it
        assert seq0 != seq1
        assert any(seq0) and not all(seq0)

    def test_budget_caps_total_fires(self):
        set_config(chaos="3:1.0:fail:2")
        fired = 0
        for _ in range(6):
            try:
                faults.maybe_fault("stream.read")
            except faults.InjectedTransientError:
                fired += 1
        assert fired == 2

    def test_kinds_cycle_deterministically(self):
        set_config(chaos="3:1.0:fail+oom")
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("stream.read")
        with pytest.raises(faults.InjectedOOMError):
            faults.maybe_fault("stream.read")
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("stream.read")

    def test_chaos_layers_on_top_of_explicit_spec(self):
        set_config(fault_spec="stream.read:err=1", chaos="3:1.0:fail:1")
        with pytest.raises(faults.InjectedPermanentError):
            faults.maybe_fault("stream.read")  # explicit spec wins first
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("stream.read")  # then the chaos schedule
        faults.maybe_fault("stream.read")  # both budgets spent

    def test_stats_expose_chaos_counters(self):
        # the registry is process-global and re-arms on spec CHANGE, so
        # each test uses a unique spec string (fresh counters)
        set_config(chaos="31:1.0:fail:1")
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("prefetch.stage")
        st = faults.stats()["chaos"]
        assert st["fired"] == 1 and st["calls"] == {"prefetch.stage": 1}

    def test_rearms_on_spec_change(self):
        set_config(chaos="32:1.0:fail:1")
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("stream.read")
        faults.maybe_fault("stream.read")  # budget spent
        set_config(chaos="33:1.0:fail:1")  # new spec -> fresh budget
        with pytest.raises(faults.InjectedTransientError):
            faults.maybe_fault("stream.read")

    def test_kill_kind_sigkills_the_process(self, tmp_path):
        """``kill`` is a real SIGKILL (a preemption), not an exception —
        proven in a subprocess; the fault_spec grammar accepts it too."""
        proc = subprocess.run(
            [sys.executable, "-c",
             "from oap_mllib_tpu.utils import faults\n"
             "faults.maybe_fault('stream.read')\n"
             "print('SURVIVED')"],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "OAP_MLLIB_TPU_FAULT_SPEC": "stream.read:kill=1",
                 "PYTHONPATH": _REPO},
            capture_output=True, text=True, timeout=120, cwd=_REPO,
        )
        assert proc.returncode == -9, proc.stdout + proc.stderr
        assert "SURVIVED" not in proc.stdout


class TestCollectiveDispatchSite:
    def test_site_is_registered(self):
        assert "collective.dispatch" in faults.SITES

    def test_facade_dispatch_is_injectable(self, rng):
        """The satellite: faults.maybe_fault threads through the eager
        collective facade, so the recovery drills can fault the exact
        seam where a dead peer would surface."""
        from oap_mllib_tpu.parallel import collective
        from oap_mllib_tpu.parallel.mesh import get_mesh

        import jax.numpy as jnp

        mesh = get_mesh()
        x = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
        set_config(fault_spec="collective.dispatch:fail=1")
        with pytest.raises(faults.InjectedTransientError):
            collective.allreduce_sum(x, mesh)
        set_config(fault_spec="")
        # healthy dispatch: each device's (1, 4) shard sums to the
        # replicated (1, 4) result
        out = collective.allreduce_sum(x, mesh)
        np.testing.assert_allclose(
            np.asarray(out)[0], np.asarray(x).sum(axis=0), rtol=1e-5
        )


def _two_process(monkeypatch, rank=0):
    monkeypatch.setattr(recovery, "_world", lambda: 2)
    monkeypatch.setattr(recovery, "_rank", lambda: rank)


class TestWatchdog:
    def test_fast_dispatch_passes_through_and_fingerprints(self, monkeypatch):
        _two_process(monkeypatch)
        set_config(collective_timeout=5.0)
        before = recovery.last_completed()["count"]
        assert recovery.guarded_dispatch("psum", "data", lambda: 7) == 7
        after = recovery.last_completed()
        assert after["count"] == before + 1
        assert after["last"] == "psum|data"

    def test_worker_exception_propagates(self, monkeypatch):
        _two_process(monkeypatch)
        set_config(collective_timeout=5.0)

        def boom():
            raise RuntimeError("inner failure")

        with pytest.raises(RuntimeError, match="inner failure"):
            recovery.guarded_dispatch("psum", "data", boom)

    def test_timeout_raises_named_diagnosis(self, monkeypatch, tmp_path):
        _two_process(monkeypatch)
        crash = str(tmp_path / "sideband")
        set_config(collective_timeout=0.3, crash_dir=crash)
        t0 = time.monotonic()
        with pytest.raises(recovery.CollectiveTimeoutError) as ei:
            recovery.guarded_dispatch(
                "allreduce_sum", "data", lambda: time.sleep(3)
            )
        assert time.monotonic() - t0 < 2.0
        e = ei.value
        assert e.op == "allreduce_sum" and e.axis == "data"
        assert e.elapsed_s >= 0.3
        msg = str(e)
        assert "allreduce_sum" in msg and "collective_timeout=0.3" in msg
        assert "Recovery:" in msg  # the runbook pointer
        # the survivor's crash record landed in the sideband
        rec = json.load(open(recovery.crash_record_path(crash, 0)))
        assert rec["fault_class"] == recovery.FAULT_TIMEOUT
        assert rec["op"] == "allreduce_sum"

    def test_timeout_metrics_counted(self, monkeypatch):
        from oap_mllib_tpu.telemetry import metrics as tm

        _two_process(monkeypatch)
        set_config(collective_timeout=0.2)
        before = tm.counter(
            "oap_recovery_timeouts_total", {"op": "psum"}).value
        with pytest.raises(recovery.CollectiveTimeoutError):
            recovery.guarded_dispatch("psum", "data", lambda: time.sleep(2))
        assert tm.counter(
            "oap_recovery_timeouts_total", {"op": "psum"}
        ).value == before + 1

    def test_peer_poison_aborts_promptly(self, monkeypatch, tmp_path):
        """A peer's crash record must beat the deadline by a wide margin:
        the whole point of the sideband is not burning the full timeout
        when the fault is already diagnosed."""
        _two_process(monkeypatch)
        crash = str(tmp_path / "sideband")
        os.makedirs(crash)
        with open(recovery.crash_record_path(crash, 1), "w") as f:
            json.dump({"rank": 1, "fault_class": "oom", "site": "als.fit",
                       "error": "boom", "last_checkpoint_step": 5}, f)
        set_config(collective_timeout=30.0, crash_dir=crash)
        t0 = time.monotonic()
        with pytest.raises(recovery.PeerAbortError) as ei:
            recovery.guarded_dispatch(
                "process_allgather", "host", lambda: time.sleep(30)
            )
        assert time.monotonic() - t0 < 5.0  # nowhere near the 30s deadline
        assert ei.value.record["rank"] == 1
        msg = str(ei.value)
        assert "rank 1" in msg and "oom" in msg and "als.fit" in msg
        assert "checkpoint step was 5" in msg
        # the victim wrote its own record too (machine-readable on EVERY rank)
        rec = json.load(open(recovery.crash_record_path(crash, 0)))
        assert rec["fault_class"] == recovery.FAULT_PEER_ABORT

    # what the installed jax's Gloo transport raises from a collective
    # whose peer was SIGKILLed (tests/test_pseudo_cluster.py kill legs)
    _GLOO_RESET = (
        "UNKNOWN: Gloo AllGather failed: [external/gloo/gloo/transport/"
        "tcp/pair.cc:538] Read error [127.0.0.1]:30084: Connection reset "
        "by peer"
    )
    _GLOO_CLOSED = (
        "UNKNOWN: Gloo AllGather failed: [external/gloo/gloo/transport/"
        "tcp/pair.cc:547] Connection closed by peer [127.0.0.1]:37009"
    )
    _HEARTBEAT = (
        "UNAVAILABLE: Task /job:jax_worker/replica:0/task:1 heartbeat "
        "timeout. This indicates that the remote task has failed"
    )

    @pytest.mark.parametrize("armed", [True, False],
                             ids=["deadline-armed", "disarmed"])
    @pytest.mark.parametrize("text", [_GLOO_RESET, _GLOO_CLOSED, _HEARTBEAT],
                             ids=["reset", "closed", "heartbeat"])
    def test_lost_peer_socket_is_a_peer_abort(self, monkeypatch, tmp_path,
                                              armed, text):
        """A collective that FAILS because its peer is gone is the same
        event as one that hangs to the deadline, reported sooner: a
        PeerAbortError (a RecoveryError) with this rank's crash record,
        whether or not a deadline is armed."""
        _two_process(monkeypatch)
        crash = str(tmp_path / "sideband")
        set_config(collective_timeout=5.0 if armed else 0.0,
                   crash_dir=crash)

        def gloo_fails():
            raise ValueError(text)

        with pytest.raises(recovery.PeerAbortError) as ei:
            recovery.guarded_dispatch("process_allgather", "host",
                                      gloo_fails)
        assert isinstance(ei.value, recovery.RecoveryError)
        assert isinstance(ei.value.__cause__, ValueError)
        assert "process_allgather" in str(ei.value)
        assert text[-20:] in str(ei.value)
        assert ei.value.record["fault_class"] == recovery.FAULT_PEER_ABORT
        rec = json.load(open(recovery.crash_record_path(crash, 0)))
        assert rec["fault_class"] == recovery.FAULT_PEER_ABORT
        assert rec["op"] == "process_allgather"

    def test_a_client_socket_reset_is_not_a_lost_peer(self, monkeypatch):
        """No collective transport named: a reset of a client socket (a
        coordinator connect, a file server) is not this plane's to
        claim, even in a two-process world — it propagates raw and
        ``resilience.classify_fault`` still reads it as TRANSIENT."""
        from oap_mllib_tpu.utils import resilience

        _two_process(monkeypatch)
        exc = RuntimeError("Connection reset by peer")

        def fails():
            raise exc

        with pytest.raises(RuntimeError) as ei:
            recovery.guarded_dispatch("psum", "data", fails)
        assert ei.value is exc
        assert resilience.classify_fault(exc) == resilience.TRANSIENT

    def test_lost_peer_is_not_claimed_single_process(self):
        """world==1 has no peer to lose: the error propagates raw."""
        with pytest.raises(ValueError, match="Gloo"):
            recovery.guarded_dispatch(
                "psum", "data",
                lambda: (_ for _ in ()).throw(ValueError(self._GLOO_RESET)),
            )

    def test_single_process_never_watches(self):
        """world==1: armed or not, the dispatch runs inline (there is no
        peer to wait for)."""
        set_config(collective_timeout=0.05)
        t0 = time.monotonic()
        assert recovery.guarded_dispatch(
            "psum", "data", lambda: (time.sleep(0.2), 9)[1]
        ) == 9
        assert time.monotonic() - t0 >= 0.2  # ran to completion, no timeout


class TestCrashRecords:
    def test_disarmed_is_noop(self, tmp_path):
        set_config(crash_dir="")
        assert recovery.write_crash_record("s", "oom", "x") is None

    def test_record_schema(self, tmp_path):
        crash = str(tmp_path / "sideband")
        set_config(crash_dir=crash)
        path = recovery.write_crash_record(
            "kmeans.fit", "transient", "connection reset", op="psum",
            elapsed_s=1.25,
        )
        rec = json.load(open(path))
        assert rec["version"] == recovery.CRASH_RECORD_VERSION
        assert rec["rank"] == 0 and rec["world"] >= 1
        assert rec["site"] == "kmeans.fit"
        assert rec["fault_class"] == "transient"
        assert rec["op"] == "psum" and rec["elapsed_s"] == 1.25
        # the durable-step tracker is process-global, so earlier
        # checkpoint tests in a full-suite run may have advanced it —
        # only its presence and type are this test's contract
        assert isinstance(rec["last_checkpoint_step"], int)
        assert rec["last_checkpoint_step"] >= -1
        assert isinstance(rec["telemetry"], dict)
        assert "last_completed" in rec

    def test_record_carries_last_durable_checkpoint_step(self, tmp_path):
        from oap_mllib_tpu.utils import checkpoint as ckpt

        crash = str(tmp_path / "sideband")
        set_config(crash_dir=crash)
        prev = ckpt._LAST_DURABLE["step"]
        try:
            ckpt._note_durable(7)
            path = recovery.write_crash_record("s", "oom", "x")
            assert json.load(open(path))["last_checkpoint_step"] >= 7
        finally:
            with ckpt._durable_lock:
                ckpt._LAST_DURABLE["step"] = prev

    def test_check_poison_ignores_self_and_parses_peers(self, tmp_path):
        d = str(tmp_path)
        with open(recovery.crash_record_path(d, 0), "w") as f:
            json.dump({"rank": 0, "fault_class": "oom"}, f)
        assert recovery.check_poison(d, 0) is None  # own record ignored
        with open(recovery.crash_record_path(d, 2), "w") as f:
            json.dump({"rank": 2, "fault_class": "killed"}, f)
        assert recovery.check_poison(d, 0)["rank"] == 2

    def test_torn_record_still_poisons(self, tmp_path):
        d = str(tmp_path)
        with open(recovery.crash_record_path(d, 1), "w") as f:
            f.write("{not json")
        rec = recovery.check_poison(d, 0)
        assert rec == {"rank": 1}  # a half-dead peer is still dead

    def test_clear_crash_records(self, tmp_path):
        d = str(tmp_path)
        for r in (0, 1):
            with open(recovery.crash_record_path(d, r), "w") as f:
                json.dump({"rank": r}, f)
        assert recovery.clear_crash_records(d) == 2
        assert recovery.check_poison(d, 99) is None


class TestSupervisedLadder:
    def _fit(self, monkeypatch, world, crash_dir, fn=lambda d: "ok"):
        from oap_mllib_tpu.utils import resilience

        monkeypatch.setattr(resilience, "_world", lambda: world)
        if world > 1:
            monkeypatch.setattr(recovery, "_world", lambda: world)
        set_config(crash_dir=crash_dir)
        stats = resilience.ResilienceStats()
        out = resilience.resilient_fit("kmeans", fn, None, stats=stats)
        return out, stats

    def test_multiprocess_without_sideband_stays_bypassed(self, monkeypatch):
        _, stats = self._fit(monkeypatch, 2, "")
        assert stats.ladder == "bypassed(static-world)"

    def test_multiprocess_with_sideband_is_supervised(self, monkeypatch,
                                                      tmp_path):
        _, stats = self._fit(monkeypatch, 2, str(tmp_path / "sb"))
        assert stats.ladder == "supervised"

    def test_single_process_stays_active(self, monkeypatch, tmp_path):
        _, stats = self._fit(monkeypatch, 1, str(tmp_path / "sb"))
        assert stats.ladder == "active"

    def test_fatal_fault_poisons_and_propagates_unchanged(self, monkeypatch,
                                                          tmp_path):
        crash = str(tmp_path / "sb")

        def boom(degraded):
            raise MemoryError("RESOURCE_EXHAUSTED: drill")

        with pytest.raises(MemoryError, match="drill"):
            self._fit(monkeypatch, 2, crash, boom)
        rec = json.load(open(recovery.crash_record_path(crash, 0)))
        assert rec["site"] == "kmeans.fit"
        assert rec["fault_class"] == "oom"

    def test_recovery_errors_do_not_double_record(self, monkeypatch,
                                                  tmp_path):
        """A CollectiveTimeoutError reaching resilient_fit was already
        recorded at the dispatch seam — record_fatal must not overwrite
        the precise record with a generic one."""
        crash = str(tmp_path / "sb")

        def boom(degraded):
            raise recovery.CollectiveTimeoutError("already recorded")

        with pytest.raises(recovery.CollectiveTimeoutError):
            self._fit(monkeypatch, 2, crash, boom)
        assert not os.path.exists(recovery.crash_record_path(crash, 0))
