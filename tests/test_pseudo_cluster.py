"""2-process pseudo-cluster integration test.

The reference's single most important distributed test is the 2-executor
pseudo-YARN cluster that forms a real 2-rank oneCCL world on one machine
(reference dev/ci-test.sh:60-62, dev/test-cluster/).  This is its analog:
two subprocesses join a real ``jax.distributed`` world over 127.0.0.1 (CPU
backend, 2 local devices each -> a 4-device global mesh), ingest
process-local data shards via ``DenseTable.from_process_local``, fit
K-Means (unweighted + weighted) and PCA, and the parent asserts the global
results equal the single-process oracle.

Runs unconditionally in dev/ci.sh as part of the suite.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "pseudo_cluster_worker.py")
_WORKER3 = os.path.join(os.path.dirname(__file__), "pseudo_cluster_worker3.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env():
    env = dict(os.environ)
    # workers pick their own device count; strip the parent suite's 8-device
    # forcing and pin the platform via env too (belt and braces)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = " ".join(
        f for f in flags.split() if "xla_force_host_platform_device_count" not in f
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


# Environment-incapability signatures: a worker that died on one of these
# means this HOST cannot form a multiprocess jax world at all (a jax build
# whose CPU backend lacks multiprocess collectives, a sandbox that blocks
# the coordinator socket) — not a code regression.  _launch_world skips
# the suite with the captured output instead of erroring 16 tests.
_ENV_FAILURE_MARKERS = (
    "Multiprocess computations aren't implemented",
    "UNIMPLEMENTED",
    "Unable to initialize backend",
    "failed to join world",
    "DEADLINE_EXCEEDED",
    "Failed to connect to coordinator",
)


def _skip_if_environment_cannot_spawn(procs, outs):
    """pytest.skip (with the worker's captured stderr) when any worker hit
    a known environment-incapability signature.  Checked regardless of
    exit code: the error-injection worker catches exceptions itself and
    exits 0 even when what it caught was the environment, not the fault
    under test.  A worker that fails any OTHER way falls through to the
    caller's assertions — genuine regressions must still fail loudly."""
    for p, out in zip(procs, outs):
        if any(m in out for m in _ENV_FAILURE_MARKERS):
            pytest.skip(
                "pseudo-cluster world cannot run in this environment "
                f"(worker exit {p.returncode}); captured output:\n"
                + out[-2000:]
            )


def _launch_world(nproc=2, local_dev=2, timeout=300, worker=_WORKER,
                  env_extra=None):
    """Spawn an nproc world and collect (procs, outs, elapsed_sec) —
    the shared plumbing; callers interpret success/failure (the happy
    -path suites demand RESULT lines, the error-injection test demands
    prompt collective failure).  Worlds this environment cannot spawn
    at all skip the calling test instead of erroring it.  ``env_extra``
    rides into the workers' environment (worker mode switches)."""
    import time

    from oap_mllib_tpu.parallel.bootstrap import free_port

    coord = f"127.0.0.1:{free_port('127.0.0.1', 4000)}"
    env = _worker_env()
    if env_extra:
        env.update(env_extra)
    t0 = time.monotonic()
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(r), str(nproc), coord, str(local_dev)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=_REPO,
        )
        for r in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    _skip_if_environment_cannot_spawn(procs, outs)
    return procs, outs, time.monotonic() - t0


def _run_world(nproc=2, local_dev=2, timeout=300, worker=_WORKER,
               env_extra=None):
    procs, outs, _ = _launch_world(nproc, local_dev, timeout, worker,
                                   env_extra)
    results = {}
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}"
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert line, f"no RESULT line in worker output:\n{out}"
        r = json.loads(line[-1][len("RESULT "):])
        results[r["rank"]] = r
    return results


@pytest.fixture(scope="module")
def world_results():
    return _run_world()


@pytest.fixture(scope="module")
def world3_results():
    """3-process world, 1 device each, uneven thirds (1300/1300/1400)."""
    return _run_world(nproc=3, local_dev=1, worker=_WORKER3)


def _oracle_data():
    rng = np.random.default_rng(123)  # must match pseudo_cluster_worker.py
    proto = rng.normal(size=(5, 12)).astype(np.float32) * 3.0
    x = (proto[rng.integers(5, size=4000)]
         + rng.normal(size=(4000, 12)).astype(np.float32) * 0.25)
    return x


def _als_oracle_ratings():
    rng = np.random.default_rng(77)  # must match pseudo_cluster_worker.py
    nu, ni, rank = 60, 40, 3
    xt = rng.normal(size=(nu, rank)).astype(np.float32)
    yt = rng.normal(size=(ni, rank)).astype(np.float32)
    u = rng.integers(nu, size=1200).astype(np.int64)
    i = rng.integers(ni, size=1200).astype(np.int64)
    u[0], i[0] = nu - 1, ni - 1
    r = ((xt[u] * yt[i]).sum(1)
         + rng.normal(size=1200).astype(np.float32) * 0.1).astype(np.float32)
    return u, i, r


class TestPseudoCluster:
    def test_kmeans_matches_single_process(self, world_results):
        """Default (k-means||) init: the device-side rounds run multi-host
        and the converged objective matches the single-process fit."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _oracle_data()
        oracle = KMeans(k=5, seed=7, max_iter=30).fit(x)
        for rank in (0, 1):
            r = world_results[rank]
            assert r["kmeans_iters"] == oracle.summary.num_iter
            np.testing.assert_allclose(
                r["kmeans_cost"], oracle.summary.training_cost, rtol=1e-4
            )

    def test_uneven_shards_match_single_process(self, world_results):
        """1999 + 2000 valid rows: per-process padding sits mid-array, and
        random init must map valid indices around it (a padding row as a
        centroid, or an unreachable tail row, would shift the cost)."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _oracle_data()[:3999]
        oracle = KMeans(k=5, seed=11, init_mode="random", max_iter=15).fit(x)
        for rank in (0, 1):
            np.testing.assert_allclose(
                world_results[rank]["uneven_cost"],
                oracle.summary.training_cost,
                rtol=1e-4,
            )

    def test_weighted_kmeans_matches_single_process(self, world_results):
        """sample_weight through the collective per-process path (the
        round-1 multi-host weighted fit was a shape-mismatch crash)."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _oracle_data()
        w = np.ones((4000,), np.float32)
        w[:100] = 2.5  # rank 0's first 100 rows
        w[2000:2100] = 2.5  # rank 1's first 100 rows
        oracle = KMeans(k=5, seed=7, init_mode="random", max_iter=10).fit(
            x, sample_weight=w
        )
        for rank in (0, 1):
            np.testing.assert_allclose(
                world_results[rank]["weighted_cost"],
                oracle.summary.training_cost,
                rtol=1e-4,
            )

    def test_model_axis_matches_single_process(self, world_results):
        """model_parallel=2 across the 2-process world: the feature-sharded
        K-Means Lloyd and model-sharded PCA Gram agree with single-process
        model_parallel=1 oracles."""
        from oap_mllib_tpu.models.kmeans import KMeans
        from oap_mllib_tpu.models.pca import PCA

        x = _oracle_data()
        km = KMeans(k=5, seed=7, init_mode="random", max_iter=15).fit(x)
        pc = PCA(k=4).fit(x)
        for rank in (0, 1):
            r = world_results[rank]
            assert r["kmeans_mp_iters"] == km.summary.num_iter
            np.testing.assert_allclose(
                r["kmeans_mp_cost"], km.summary.training_cost, rtol=1e-3
            )
            np.testing.assert_allclose(
                r["pca_mp_var"], np.asarray(pc.explained_variance_), rtol=1e-3
            )

    def test_pca_matches_single_process(self, world_results):
        from oap_mllib_tpu.models.pca import PCA

        x = _oracle_data()
        oracle = PCA(k=4).fit(x)
        for rank in (0, 1):
            r = world_results[rank]
            np.testing.assert_allclose(
                r["pca_var"], np.asarray(oracle.explained_variance_), rtol=1e-3
            )
            # eigenvector sign is arbitrary: compare |PC0| (the reference's
            # sign-insensitive pattern, IntelPCASuite.scala:80-86)
            np.testing.assert_allclose(
                r["pca_pc0_abs"],
                np.abs(np.asarray(oracle.components_)[:, 0]),
                atol=1e-4,
            )

    @pytest.mark.parametrize("tag,implicit", [("imp", True), ("exp", False)])
    def test_als_matches_single_process(self, world_results, tag, implicit):
        """Each rank fed only its local ratings shard (590/610 uneven
        split); factors must match the single-process fit.  Exercises the
        multi-process branches of exchange_ratings, the allgathered
        id-maxima, and the rank-local sharded-factor gather.  Tolerance is
        2x the block-vs-oracle bar since both sides carry f32 error."""
        from oap_mllib_tpu.models.als import ALS

        u, i, r = _als_oracle_ratings()
        oracle = ALS(rank=3, max_iter=3, reg_param=0.1, alpha=0.8,
                     implicit_prefs=implicit, seed=3).fit(u, i, r)
        for rank in (0, 1):
            res = world_results[rank]
            np.testing.assert_allclose(
                res[f"als_{tag}_uf"], oracle.user_factors_,
                atol=4e-3, rtol=4e-3,
            )
            np.testing.assert_allclose(
                res[f"als_{tag}_if"], oracle.item_factors_,
                atol=4e-3, rtol=4e-3,
            )

    def test_als_item_sharded_matches_single_process(self, world_results):
        """als_item_layout="sharded" across the real 2-process world: the
        second (item-block) shuffle, the all_gather exchange loop, and
        the collective item-factor gather must land on the same factors
        as the single-process fit."""
        from oap_mllib_tpu.models.als import ALS

        u, i, r = _als_oracle_ratings()
        oracle = ALS(rank=3, max_iter=3, reg_param=0.1, alpha=0.8,
                     implicit_prefs=True, seed=3).fit(u, i, r)
        for rank in (0, 1):
            res = world_results[rank]
            np.testing.assert_allclose(
                res["als_sh_uf"], oracle.user_factors_, atol=4e-3, rtol=4e-3
            )
            np.testing.assert_allclose(
                res["als_sh_if"], oracle.item_factors_, atol=4e-3, rtol=4e-3
            )
        assert world_results[0]["als_sh_if"] == world_results[1]["als_sh_if"]

    def test_streamed_kmeans_matches_single_process(self, world_results):
        """Each rank streams its local half as a ChunkSource; the
        host-mediated cross-process reductions must land on the same
        clustering quality as the single-process streamed fit (init RNG
        merges differ across world sizes, so compare cost — survey §7.3)."""
        from oap_mllib_tpu.data.stream import ChunkSource
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _oracle_data()
        # not the workers' seed 7: in ONE process that seed's k-means++
        # draws put two centres into one blob and Lloyd stays there (cost
        # 67487 against 2990.7; on this table 1 seed in 40 does so, a
        # property of D^2 seeding — P about 3% at the last of the five
        # draws — not of a world size), and the premise below is the optimum
        oracle = KMeans(k=5, seed=8, max_iter=30).fit(
            ChunkSource.from_array(x, chunk_rows=512)
        )
        for rank in (0, 1):
            r = world_results[rank]
            # well-separated blobs: both reach the same optimum
            np.testing.assert_allclose(
                r["streamed_cost"], oracle.summary.training_cost, rtol=1e-3
            )
            np.testing.assert_allclose(
                r["streamed_rand_cost"], oracle.summary.training_cost,
                rtol=1e-3,
            )

    def test_streamed_pca_matches_single_process(self, world_results):
        """Streamed PCA over per-process shards == streamed PCA over the
        full table (exact moments, fp tolerance only)."""
        from oap_mllib_tpu.models.pca import PCA

        x = _oracle_data()
        oracle = PCA(k=4).fit(x)
        for rank in (0, 1):
            r = world_results[rank]
            np.testing.assert_allclose(
                r["streamed_pca_var"],
                np.asarray(oracle.explained_variance_), rtol=1e-3,
            )
            np.testing.assert_allclose(
                r["streamed_pca_pc0_abs"],
                np.abs(np.asarray(oracle.components_)[:, 0]), atol=1e-4,
            )

    def test_three_process_world(self, world3_results):
        """Uneven thirds over 3 processes (a world size the reference
        never tested): in-memory mesh AND streamed per-process-source
        fits match the single-process oracles; all ranks agree."""
        from oap_mllib_tpu.models.kmeans import KMeans
        from oap_mllib_tpu.models.pca import PCA

        x = _oracle_data()
        km = KMeans(k=5, seed=7, max_iter=30).fit(x)
        pc = PCA(k=4).fit(x)
        for rank in (0, 1, 2):
            r = world3_results[rank]
            np.testing.assert_allclose(
                r["kmeans_cost"], km.summary.training_cost, rtol=1e-3
            )
            np.testing.assert_allclose(
                r["pca_var"], np.asarray(pc.explained_variance_), rtol=1e-3
            )
            np.testing.assert_allclose(
                r["streamed_cost"], km.summary.training_cost, rtol=1e-3
            )
            np.testing.assert_allclose(
                r["streamed_pca_var"],
                np.asarray(pc.explained_variance_), rtol=1e-3,
            )
        assert world3_results[0] == {**world3_results[0], **{
            k: v for k, v in world3_results[1].items() if k != "rank"
        }}
        assert (
            world3_results[1]["streamed_cost"]
            == world3_results[2]["streamed_cost"]
        )

    def test_three_process_item_sharded_als(self, world3_results):
        """als_item_layout="sharded" over 3 ranks (a block count that is
        neither 2 nor a power of two — the last item block is short):
        factors match the single-process fit on the same global edges."""
        from oap_mllib_tpu.models.als import ALS

        rng_als = np.random.default_rng(77)
        nu, ni = 60, 40
        u = rng_als.integers(nu, size=1200).astype(np.int64)
        i = rng_als.integers(ni, size=1200).astype(np.int64)
        u[0], i[0] = nu - 1, ni - 1
        r = rng_als.random(1200).astype(np.float32) * 4 + 1
        oracle = ALS(rank=3, max_iter=3, reg_param=0.1,
                     implicit_prefs=True, seed=3).fit(u, i, r)
        for rank in (0, 1, 2):
            np.testing.assert_allclose(
                world3_results[rank]["als_sh_if"], oracle.item_factors_,
                atol=4e-3, rtol=4e-3,
            )
            # streamed-block 2-D over the same 3-rank world (short last
            # item block through the cross-process double redistribution)
            np.testing.assert_allclose(
                world3_results[rank]["als_st3_if"], oracle.item_factors_,
                atol=4e-3, rtol=4e-3,
            )

    def test_streamed_block_als_two_process(self, world_results):
        """Out-of-core ALS composed with a REAL 2-process world: each
        rank streamed only its local triples; the block redistribution
        ran over the process boundary and the chunked uploads + block
        collectives must land on the single-process factors."""
        from oap_mllib_tpu.models.als import ALS

        u, i, r = _als_oracle_ratings()
        oracle = ALS(rank=3, max_iter=3, reg_param=0.1, alpha=0.8,
                     implicit_prefs=True, seed=3).fit(u, i, r)
        for rank in (0, 1):
            res = world_results[rank]
            np.testing.assert_allclose(
                res["als_st_uf"], oracle.user_factors_,
                atol=4e-3, rtol=4e-3,
            )
            np.testing.assert_allclose(
                res["als_st_if"], oracle.item_factors_,
                atol=4e-3, rtol=4e-3,
            )
            # 2-D item-sharded streamed composition (double
            # redistribution + cross-process replicate + collective
            # factor gathers) lands on the same factors
            np.testing.assert_allclose(
                res["als_st_sh_uf"], oracle.user_factors_,
                atol=4e-3, rtol=4e-3,
            )
            np.testing.assert_allclose(
                res["als_st_sh_if"], oracle.item_factors_,
                atol=4e-3, rtol=4e-3,
            )
        assert world_results[0]["als_st_if"] == world_results[1]["als_st_if"]
        assert (
            world_results[0]["als_st_sh_if"]
            == world_results[1]["als_st_sh_if"]
        )

    def test_adapter_partitioned_kmeans(self, world_results):
        """The PySpark adapter's multi-process ingestion: each rank
        materialized only its partitions of a mocked partitioned
        DataFrame (pid % world == rank) and fed them as its local shard;
        the converged cost must match the single-process fit on the full
        data, and both ranks must agree exactly."""
        from oap_mllib_tpu.models.kmeans import KMeans

        x = _oracle_data()
        oracle = KMeans(k=5, seed=7, max_iter=30).fit(x)
        for rank in (0, 1):
            np.testing.assert_allclose(
                world_results[rank]["adapter_mp_cost"],
                oracle.summary.training_cost, rtol=1e-3,
            )
        assert (
            world_results[0]["adapter_mp_cost"]
            == world_results[1]["adapter_mp_cost"]
        )

    def test_adapter_partitioned_als(self, world_results):
        """Adapter ALS over partitioned ratings: factors match the
        single-process fit, and the cold-start seen-user sets are
        WORLD-consistent (global uniques, not rank-local) — rank-local
        sets would drop different rows on different ranks."""
        from oap_mllib_tpu.models.als import ALS

        u, i, r = _als_oracle_ratings()
        oracle = ALS(rank=3, max_iter=3, reg_param=0.1, alpha=0.8,
                     implicit_prefs=True, seed=3).fit(u, i, r)
        expect_seen = sorted(int(v) for v in np.unique(u))
        for rank in (0, 1):
            res = world_results[rank]
            np.testing.assert_allclose(
                res["adapter_als_uf"], oracle.user_factors_,
                atol=4e-3, rtol=4e-3,
            )
            assert res["adapter_seen_users"] == expect_seen
        assert (
            world_results[0]["adapter_als_uf"]
            == world_results[1]["adapter_als_uf"]
        )

    def test_source_error_fails_world_fast(self):
        """The _PassGuard contract in a REAL 2-process world: rank 1's
        source errors mid-pass, and BOTH ranks must raise out of the
        same fit promptly — not hang in process_allgather until the
        distributed timeout (the pre-round-4 behavior)."""
        worker = os.path.join(
            os.path.dirname(__file__), "pseudo_cluster_worker_err.py"
        )
        procs, outs, elapsed = _launch_world(
            nproc=2, local_dev=1, timeout=120, worker=worker
        )
        for p, out in zip(procs, outs):
            assert p.returncode == 0, f"worker did not see the error:\n{out}"
            assert "EXPECTED_ERROR" in out, out
        # rank 0's source is consistent — its failure can only be the
        # guard flag riding the collective (the mechanism under test)
        assert "RuntimeError: streamed pass failed" in outs[0], outs[0]
        assert "deterministic" in outs[1], outs[1]  # the original error
        # both ranks failed together, well under any distributed timeout
        assert elapsed < 90, f"world took {elapsed:.0f}s to fail"

    def test_ranks_agree(self, world_results):
        """Replicated results must be bitwise-identical across ranks."""
        assert world_results[0]["kmeans_cost"] == world_results[1]["kmeans_cost"]
        assert world_results[0]["pca_var"] == world_results[1]["pca_var"]
        assert world_results[0]["als_imp_if"] == world_results[1]["als_imp_if"]
        assert world_results[0]["streamed_cost"] == world_results[1]["streamed_cost"]
        assert (
            world_results[0]["streamed_pca_var"]
            == world_results[1]["streamed_pca_var"]
        )


_SANITIZER_WORKER = os.path.join(
    os.path.dirname(__file__), "pseudo_cluster_worker_sanitizer.py"
)
_CKPT_WORKER = os.path.join(
    os.path.dirname(__file__), "pseudo_cluster_worker_ckpt.py"
)


class TestElasticWorlds:
    """ISSUE 8 acceptance: kill-and-resume across a REAL 2-process world
    (utils/checkpoint.py), plus the 2->1 resharded restore."""

    def _launch_kill_world(self, ckdir, timeout=240):
        """Victim world: rank 1 hard-kills itself mid-pass; rank 0 is
        left in the pass collective and reaped by this watchdog — the
        preemption the elastic-worlds subsystem exists for."""
        import time

        from oap_mllib_tpu.parallel.bootstrap import free_port

        coord = f"127.0.0.1:{free_port('127.0.0.1', 4000)}"
        env = _worker_env()
        env.update({
            "CKPT_WORKER_MODE": "victim", "CKPT_CHECKPOINT_DIR": ckdir,
        })
        procs = [
            subprocess.Popen(
                [sys.executable, _CKPT_WORKER, str(r), "2", coord, "1"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=_REPO,
            )
            for r in range(2)
        ]
        deadline = time.monotonic() + timeout
        while procs[1].poll() is None and time.monotonic() < deadline:
            time.sleep(0.5)
        # rank 0 has lost its peer; give it a moment, then reap it
        grace = time.monotonic() + 20
        while procs[0].poll() is None and time.monotonic() < grace:
            time.sleep(0.5)
        outs = []
        for p in procs:
            if p.poll() is None:
                p.kill()
            out, _ = p.communicate(timeout=60)
            outs.append(out)
        _skip_if_environment_cannot_spawn(procs, outs)
        return procs, outs

    def test_kill_and_resume_matches_uninterrupted(self, tmp_path):
        full_dir = str(tmp_path / "full")
        kill_dir = str(tmp_path / "kill")
        # leg 1: the uninterrupted checkpoint-armed world (the oracle)
        full = _run_world(
            nproc=2, local_dev=1, worker=_CKPT_WORKER,
            env_extra={"CKPT_WORKER_MODE": "full",
                       "CKPT_CHECKPOINT_DIR": full_dir},
        )
        assert full[0]["decision"] == "fresh"
        assert full[0]["ladder"] == "bypassed(static-world)"
        assert full[0]["centers_hex"] == full[1]["centers_hex"]

        # leg 2: the same fit, rank 1 preempted mid-pass-3
        procs, outs = self._launch_kill_world(kill_dir)
        assert procs[1].returncode == 9, outs[1]  # genuinely killed
        # passes 1-2 are durable: both rank shards + the manifest
        mdirs = os.listdir(kill_dir)
        assert len(mdirs) == 1
        manifest = json.load(
            open(os.path.join(kill_dir, mdirs[0], "manifest.json"))
        )
        assert manifest["step"] == 2 and manifest["world"] == 2

        # leg 3: a RELAUNCHED 2-process world resumes and must match the
        # uninterrupted run bit-for-bit
        resumed = _run_world(
            nproc=2, local_dev=1, worker=_CKPT_WORKER,
            env_extra={"CKPT_WORKER_MODE": "resume",
                       "CKPT_CHECKPOINT_DIR": kill_dir},
        )
        for rank in (0, 1):
            assert resumed[rank]["decision"] == "found"
            assert resumed[rank]["restored_step"] == 2
            assert resumed[rank]["centers_hex"] == full[rank]["centers_hex"]
            assert resumed[rank]["cost"] == full[rank]["cost"]

        # leg 4: 2 -> 1 resharded restore — THIS process (a 1-process
        # world) consumes the 2-rank checkpoint and must land within fp
        # tolerance of the 2-process run (reduction order changes)
        import numpy as _np

        from oap_mllib_tpu.config import set_config
        from oap_mllib_tpu.data.stream import ChunkSource
        from oap_mllib_tpu.models.kmeans import KMeans

        rng = _np.random.default_rng(321)  # must match the worker
        x = rng.normal(size=(3000, 8)).astype(_np.float32)
        set_config(checkpoint_dir=kill_dir)
        try:
            m1 = KMeans(
                k=4, seed=7, init_mode="random", max_iter=6, tol=0.0
            ).fit(ChunkSource.from_array(x, chunk_rows=500))
        finally:
            set_config(checkpoint_dir="")
        assert m1.summary.checkpoint["decision"] == "resharded"
        assert m1.summary.checkpoint["old_world"] == 2
        _np.testing.assert_allclose(
            m1.summary.training_cost, full[0]["cost"], rtol=1e-5
        )


_RECOVERY_WORKER = os.path.join(
    os.path.dirname(__file__), "pseudo_cluster_worker_recovery.py"
)

# How a survivor learns that its peer was SIGKILLed depends on the
# collective transport.  The installed jax's Gloo reports the closed
# socket at once ("Gloo AllGather failed ... Connection reset by peer"),
# which the recovery plane raises as PeerAbortError / fault class
# peer_abort; a transport that blocks instead runs into
# Config.collective_timeout (CollectiveTimeoutError / collective_timeout).
# Which one fires is the transport's choice, not the plane's: the kill
# legs below hold the plane to a PROMPT RecoveryError, a crash record
# and a self-exit, and accept either diagnosis.
_PEER_LOSS_MARKERS = ("TIMEOUT_CAUGHT", "PEER_ABORT")
_PEER_LOSS_ERRORS = ("CollectiveTimeoutError", "PeerAbortError")
_PEER_LOSS_CLASSES = ("collective_timeout", "peer_abort")


class TestLiveWorldRecovery:
    """ISSUE 10 acceptance: the recovery plane across a REAL 2-process
    world — a SIGKILLed rank converts every survivor's hang into a
    prompt CollectiveTimeoutError, and a poisoned sideband aborts peers
    out of their collectives (utils/recovery.py)."""

    def _launch_recovery_world(self, mode, crash_dir, timeout=120):
        """Spawn the 2-rank drill world.  Unlike the elastic-worlds kill
        leg, the parent never reaps the survivor: the plane under test
        is that EVERY rank exits on its own, within the deadline."""
        import time

        from oap_mllib_tpu.parallel.bootstrap import free_port

        coord = f"127.0.0.1:{free_port('127.0.0.1', 4000)}"
        env = _worker_env()
        env.update({
            "RECOVERY_WORKER_MODE": mode, "RECOVERY_CRASH_DIR": crash_dir,
        })
        t0 = time.monotonic()
        procs = [
            subprocess.Popen(
                [sys.executable, _RECOVERY_WORKER, str(r), "2", coord, "1"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=_REPO,
            )
            for r in range(2)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=timeout)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        _skip_if_environment_cannot_spawn(procs, outs)
        return procs, outs, time.monotonic() - t0

    def test_rank_kill_raises_timeout_on_survivors(self, tmp_path):
        """Satellite leg: rank 1 is SIGKILLed mid-collective; rank 0
        must raise a recovery error (see ``_PEER_LOSS_ERRORS``) within
        collective_timeout — exiting BY ITSELF, well inside the 120 s
        watchdog — with its crash record (fault class, last-completed
        fingerprint) in the sideband for the supervisor to classify."""
        crash_dir = str(tmp_path / "sideband")
        procs, outs, elapsed = self._launch_recovery_world(
            "hang", crash_dir
        )
        assert procs[1].returncode == -9, outs[1]  # genuinely SIGKILLed
        assert procs[0].returncode == 0, f"survivor did not self-exit:\n{outs[0]}"
        assert any(m in outs[0] for m in _PEER_LOSS_MARKERS), outs[0]
        # the survivor's diagnosis landed in the sideband, machine-readable
        rec_path = os.path.join(crash_dir, "crash.rank0.json")
        assert os.path.exists(rec_path), os.listdir(crash_dir)
        rec = json.load(open(rec_path))
        assert rec["fault_class"] in _PEER_LOSS_CLASSES
        assert rec["rank"] == 0 and rec["world"] == 2
        assert rec["last_checkpoint_step"] == -1  # no checkpointing armed
        assert "telemetry" in rec
        # the whole drill completed well under the distributed timeout
        assert elapsed < 90, f"world took {elapsed:.0f}s to diagnose"

    def test_peer_crash_record_aborts_collectives(self, tmp_path):
        """Coordinated abort: rank 1's fatal fault never reaches a
        collective — only the sideband can tell rank 0, which must
        raise PeerAbortError promptly instead of burning the full
        deadline."""
        crash_dir = str(tmp_path / "sideband")
        procs, outs, elapsed = self._launch_recovery_world(
            "abort", crash_dir
        )
        assert procs[1].returncode == 3, outs[1]
        assert "ABORT_RECORDED" in outs[1], outs[1]
        assert procs[0].returncode == 0, f"survivor did not self-exit:\n{outs[0]}"
        assert "PEER_ABORT_CAUGHT" in outs[0], outs[0]
        assert "peer=1" in outs[0], outs[0]
        # both ranks' records in the sideband: the culprit's fault and
        # the victim's abort
        recs = {
            f: json.load(open(os.path.join(crash_dir, f)))
            for f in os.listdir(crash_dir) if f.endswith(".json")
        }
        assert recs["crash.rank1.json"]["fault_class"] == "unclassified"
        assert recs["crash.rank0.json"]["fault_class"] == "peer_abort"
        assert elapsed < 90, f"world took {elapsed:.0f}s to abort"


class TestSanitizerPlane:
    """The runtime sanitizer plane (utils/sanitizers.py) across a REAL
    2-process world — the configuration it exists for."""

    def test_collective_sanitizer_names_divergence_instead_of_hanging(self):
        """ISSUE 7 acceptance: rank 0 dispatches allreduce_sum while
        rank 1 dispatches allgather_rows — without the sanitizer this
        wedges both ranks inside mismatched collectives until the
        distributed timeout; with `collective` armed, BOTH ranks must
        raise a CollectiveDivergenceError naming both ops, promptly
        (the watchdog is the 120 s world timeout)."""
        procs, outs, elapsed = _launch_world(
            nproc=2, local_dev=1, timeout=120, worker=_SANITIZER_WORKER,
            env_extra={"SANITIZER_WORKER_MODE": "diverge"},
        )
        for p, out in zip(procs, outs):
            assert p.returncode == 0, f"divergence not caught:\n{out}"
            assert "DIVERGENCE_CAUGHT" in out, out
        assert elapsed < 100, f"world took {elapsed:.0f}s to diagnose"

    @pytest.fixture(scope="class")
    def probe_results(self):
        return _run_world(
            nproc=2, local_dev=2, worker=_SANITIZER_WORKER,
            env_extra={"SANITIZER_WORKER_MODE": "probe"},
        )

    def test_facade_books_per_shard_bytes(self, probe_results):
        """ISSUE 7 satellite: the facade must book each PROCESS's shard
        bytes (half the global array here), not the unsharded abstract
        shape — so the world's byte counters sum to the wire traffic
        instead of world × payload."""
        for rank in (0, 1):
            r = probe_results[rank]
            assert r["booked_bytes"] == r["global_bytes"] / 2, r

    def test_sanitized_streamed_fit_clean_and_fingerprint_agrees(
            self, probe_results):
        """All three sanitizers armed over a streamed multi-process fit:
        the fit must succeed (no false positives from the transfer/
        retrace guards), the collective fingerprint must be world-checked
        and identical across ranks, and the costs must agree exactly."""
        r0, r1 = probe_results[0], probe_results[1]
        assert r0["san_ops"] > 0
        assert r0["san_world_checked"] and r1["san_world_checked"]
        assert r0["san_fingerprint"] == r1["san_fingerprint"]
        assert r0["streamed_cost"] == r1["streamed_cost"]


_FLEET_WORKER = os.path.join(
    os.path.dirname(__file__), "pseudo_cluster_worker_fleet.py"
)


class TestFleetObservability:
    """ISSUE 11 acceptance: the fleet control plane across a REAL
    2-process world — per-pass rollups agree on every rank, a
    deliberately slowed rank is named with skew > 1.5, the live
    /metrics endpoint serves oap_fleet_* mid-fit, and a SIGKILL
    drill's crash records carry >= 32-event flight-recorder tails."""

    def _launch_fleet_world(self, mode, env_extra=None, timeout=180):
        import time

        from oap_mllib_tpu.parallel.bootstrap import free_port

        coord = f"127.0.0.1:{free_port('127.0.0.1', 4000)}"
        env = _worker_env()
        env["FLEET_WORKER_MODE"] = mode
        env.update(env_extra or {})
        t0 = time.monotonic()
        procs = [
            subprocess.Popen(
                [sys.executable, _FLEET_WORKER, str(r), "2", coord, "1"],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=_REPO,
            )
            for r in range(2)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=timeout)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        _skip_if_environment_cannot_spawn(procs, outs)
        return procs, outs, time.monotonic() - t0

    @staticmethod
    def _tagged_json(out, tag, rank):
        line = [
            ln for ln in out.splitlines()
            if ln.startswith(f"{tag} rank={rank} ")
        ]
        assert line, f"no {tag} line for rank {rank}:\n{out}"
        return json.loads(line[0].split(" ", 2)[2])

    def test_skewed_rank_named_and_rollups_agree(self):
        """A slowed rank 1 must show up in every rank's identical fleet
        window, the summary block must name it with skew > 1.5, and
        rank 0's live endpoint must serve oap_fleet_* families while
        the fit is running."""
        from oap_mllib_tpu.parallel.bootstrap import free_port

        port = free_port("127.0.0.1", 9400)
        procs, outs, _ = self._launch_fleet_world(
            "skew", {"FLEET_METRICS_PORT": str(port)}
        )
        for p, out in zip(procs, outs):
            assert p.returncode == 0, f"worker failed:\n{out}"
        blocks = [self._tagged_json(outs[r], "FLEETBLOCK", r)
                  for r in range(2)]
        windows = [self._tagged_json(outs[r], "WINDOW", r)
                   for r in range(2)]
        # the gathered per-pass frames are identical on every rank (the
        # rollup is a rank-uniform allgather) ...
        assert windows[0] == windows[1]
        assert len(windows[0]) >= 4  # per-pass granularity: >= max_iter
        # ... and rank 0's fold equals a hand-fold of the per-rank rows
        for w in windows[0]:
            frames = np.asarray(w["frames"])
            assert frames.shape[0] == 2
            for i, field in enumerate([
                "pass_wall_s", "stage_s", "transfer_s", "compute_s",
                "bytes_staged", "retries", "kernel_dispatch_s",
            ]):
                got = w["fields"][field]
                col = frames[:, i]
                assert abs(got["mean"] - col.mean()) < 1e-9
                assert abs(got["min"] - col.min()) < 1e-9
                assert abs(got["max"] - col.max()) < 1e-9
        # the straggler analytics name the slowed rank with real skew
        for block in blocks:
            assert block["enabled"] and block["passes"] >= 4
            assert block["slowest_rank"] == 1, block
            assert block["fit_skew_ratio"] > 1.5, block
        # the live endpoint served fleet families mid-fit on rank 0
        assert "SCRAPE OK rank=0" in outs[0], outs[0]

    def test_sigkill_crash_record_carries_recorder_tail(self, tmp_path):
        """A SIGKILLed rank 1 mid-pass: the surviving rank's v2 crash
        record must embed a >= 32-event flight-recorder tail whose
        events cover chunk progress and collective dispatches — the
        "what happened just before" a post-mortem needs."""
        crash_dir = str(tmp_path / "sideband")
        procs, outs, elapsed = self._launch_fleet_world(
            "kill", {"FLEET_CRASH_DIR": crash_dir}, timeout=120
        )
        assert procs[1].returncode == -9, outs[1]
        assert procs[0].returncode == 0, outs[0]
        assert any(m in outs[0] for m in _PEER_LOSS_MARKERS), outs[0]
        rec = json.load(
            open(os.path.join(crash_dir, "crash.rank0.json"))
        )
        assert rec["version"] == 2
        tail = rec["flight_recorder"]
        assert len(tail) >= 32, f"only {len(tail)} recorder events"
        kinds = {e["kind"] for e in tail}
        assert "chunk" in kinds and "collective" in kinds, kinds
        seqs = [e["seq"] for e in tail]
        assert seqs == sorted(seqs)  # tails are seq-ordered
        assert elapsed < 90, f"world took {elapsed:.0f}s to diagnose"


_SERVING_WORKER = os.path.join(
    os.path.dirname(__file__), "pseudo_cluster_worker_serving.py"
)


def _answer_digests(out):
    """leg -> digest from a serving worker's ANSWER lines."""
    digests = {}
    for ln in out.splitlines():
        if ln.startswith("ANSWER "):
            parts = dict(p.split("=") for p in ln.split()[1:])
            digests[int(parts["leg"])] = parts["digest"]
    return digests


class TestServingPlane:
    """ISSUE 13 serving availability: a REAL 2-replica serving fleet —
    the replica that misses its collective deadline is EVICTED, the
    survivor keeps answering bit-identical results in local-only mode,
    and the supervisor's relaunched replacement answers exactly the
    same requests (serving/ha.py composed with utils/recovery.py)."""

    def test_replica_eviction_survivors_unchanged(self, tmp_path):
        crash_dir = str(tmp_path / "sideband")
        os.makedirs(crash_dir, exist_ok=True)
        procs, outs, elapsed = _launch_world(
            nproc=2, local_dev=1, timeout=120, worker=_SERVING_WORKER,
            env_extra={
                "SERVING_WORKER_MODE": "evict",
                "SERVING_CRASH_DIR": crash_dir,
            },
        )
        # rank 1 was genuinely preempted; rank 0 survived, evicted the
        # fleet, and finished EVERY serving leg
        assert procs[1].returncode == -9, outs[1]
        assert procs[0].returncode == 0, f"survivor failed:\n{outs[0]}"
        assert "EVICTED rank=0" in outs[0], outs[0]
        assert any(e in outs[0] for e in _PEER_LOSS_ERRORS), outs[0]
        assert "SERVE_OK rank=0 legs=6 local_only=True" in outs[0], outs[0]
        assert "FLEET rank=0 world=2" in outs[0], outs[0]
        survivor = _answer_digests(outs[0])
        assert sorted(survivor) == list(range(6)), survivor
        # the evicted replica answered identically while it lived
        victim = _answer_digests(outs[1])
        for leg, dig in victim.items():
            assert survivor[leg] == dig, (leg, survivor, victim)
        # the survivor's diagnosis is in the sideband for the
        # supervisor's classification
        rec = json.load(
            open(os.path.join(crash_dir, "crash.rank0.json"))
        )
        assert rec["fault_class"] in _PEER_LOSS_CLASSES
        assert elapsed < 90, f"fleet took {elapsed:.0f}s to evict"

        # the supervisor's relaunch: a replacement replica (fresh
        # 1-process world) serves the SAME requests and answers exactly
        # what the survivor answered — eviction never changed results
        procs2, outs2, _ = _launch_world(
            nproc=1, local_dev=1, timeout=120, worker=_SERVING_WORKER,
            env_extra={
                "SERVING_WORKER_MODE": "relaunched",
                "SERVING_CRASH_DIR": crash_dir,
            },
        )
        assert procs2[0].returncode == 0, outs2[0]
        assert "SERVE_OK rank=0 legs=6" in outs2[0], outs2[0]
        relaunched = _answer_digests(outs2[0])
        assert relaunched == survivor, (relaunched, survivor)


_TRAFFIC_WORKER = os.path.join(
    os.path.dirname(__file__), "pseudo_cluster_worker_traffic.py"
)


def _traffic_fields(out, tag):
    """``tag k=v ...`` line -> {k: v} from a traffic worker's output."""
    line = [ln for ln in out.splitlines() if ln.startswith(tag + " ")]
    assert line, f"no {tag} line in worker output:\n{out}"
    return dict(p.split("=", 1) for p in line[-1].split()[1:])


class TestTrafficPlane:
    """ISSUE 16 acceptance: the async traffic plane across a REAL
    2-replica serving fleet — the factor-sharded sweep names the same
    ids as the single-process reference (scores to 1e-6) on a live
    multi-process mesh, a
    jittered storm through the TrafficQueue holds the zero-steady-
    compile and p99-vs-p50 contracts, sheds stay loud, and a SIGKILLed
    replica is evicted while the survivor keeps the same contracts in
    local-only mode (serving/traffic.py + serving/ha.py)."""

    def _launch_traffic_world(self, mode, crash_dir, timeout=180):
        os.makedirs(crash_dir, exist_ok=True)
        return _launch_world(
            nproc=2, local_dev=1, timeout=timeout, worker=_TRAFFIC_WORKER,
            env_extra={
                "TRAFFIC_WORKER_MODE": mode,
                "TRAFFIC_CRASH_DIR": crash_dir,
            },
        )

    @staticmethod
    def _check_storm(out, rank, expect_local_only):
        storm = _traffic_fields(out, f"STORM_OK rank={rank}")
        assert storm["compiles"] == "0", storm
        assert storm["local_only"] == str(expect_local_only), storm
        p50, p99 = float(storm["p50_ms"]), float(storm["p99_ms"])
        # same tail bound as dev/serve_gate.py leg 5: a compile or
        # re-upload in the tail costs 100x+, scheduler jitter does not
        assert p99 <= max(50.0 * p50, 250.0), storm
        return storm

    def test_healthy_fleet_parity_storm_and_sheds(self, tmp_path):
        procs, outs, elapsed = self._launch_traffic_world(
            "healthy", str(tmp_path / "sideband")
        )
        for p, out in zip(procs, outs):
            assert p.returncode == 0, f"worker failed:\n{out}"
        # the sharded sweep agreed with each rank's IN-PROCESS single
        # -process reference, and both ranks answered identical bits
        digs = [_traffic_fields(outs[r], f"PARITY_OK rank={r}")["digest"]
                for r in range(2)]
        assert digs[0] == digs[1], digs
        for r in range(2):
            assert f"FLEET rank={r} world=2" in outs[r], outs[r]
            self._check_storm(outs[r], r, expect_local_only=False)
        assert "SHED_OK rank=0 sheds=3" in outs[0], outs[0]
        assert elapsed < 150, f"fleet took {elapsed:.0f}s"

    def test_evicted_replica_survivor_keeps_contracts(self, tmp_path):
        crash_dir = str(tmp_path / "sideband")
        procs, outs, elapsed = self._launch_traffic_world(
            "evict", crash_dir
        )
        # rank 1 genuinely preempted mid-storm; rank 0 evicted the
        # fleet and finished every wave + the shed legs on its own
        assert procs[1].returncode == -9, outs[1]
        assert procs[0].returncode == 0, f"survivor failed:\n{outs[0]}"
        assert "EVICTED rank=0" in outs[0], outs[0]
        assert any(f"err={e}" in outs[0] for e in _PEER_LOSS_ERRORS), outs[0]
        self._check_storm(outs[0], 0, expect_local_only=True)
        assert "SHED_OK rank=0 sheds=3" in outs[0], outs[0]
        # the survivor's diagnosis is in the sideband for the
        # supervisor's classification + relaunch
        rec = json.load(
            open(os.path.join(crash_dir, "crash.rank0.json"))
        )
        assert rec["fault_class"] in _PEER_LOSS_CLASSES
        assert elapsed < 150, f"fleet took {elapsed:.0f}s to evict"


_BALANCE_WORKER = os.path.join(
    os.path.dirname(__file__), "pseudo_cluster_worker_balance.py"
)


class TestHeteroFleet:
    """ISSUE 15 acceptance: capability-weighted sharding across a REAL
    2-process world with one deliberately slowed rank — the weighted
    layout beats the equal layout end-to-end, results stay within 1e-5,
    the decision trail lands in summary.balance, and the live straggler
    controller re-plans an initially-equal world mid-fit."""

    # per-chunk sleep on rank 1: equal layout pays ~12 chunks x sleep
    # per pass, the 1:0.25-weighted layout ~5 — a wide, scheduler-noise
    # -proof gap across the fit's 9 rollup passes
    _SLEEP = "0.05"

    def _launch_balance_world(self, mode, timeout=120):
        procs, outs, elapsed = _launch_world(
            nproc=2, local_dev=1, timeout=timeout, worker=_BALANCE_WORKER,
            env_extra={
                "BALANCE_WORKER_MODE": mode,
                "BALANCE_CHUNK_SLEEP": self._SLEEP,
            },
        )
        for p, out in zip(procs, outs):
            assert p.returncode == 0, f"worker failed:\n{out}"
        return outs

    @staticmethod
    def _tagged_json(out, tag, rank):
        line = [
            ln for ln in out.splitlines()
            if ln.startswith(f"{tag} rank={rank} ")
        ]
        assert line, f"no {tag} line for rank {rank}:\n{out}"
        return json.loads(line[0].split(" ", 2)[2])

    def test_weighted_layout_beats_equal_with_parity(self):
        """The capability-weighted world must finish measurably faster
        than the equal-shard world on the same slowed rank, with
        centers within 1e-5 and the plan visible in summary.balance."""
        eq = self._launch_balance_world("equal")
        wt = self._launch_balance_world("weighted")

        eq_res = [self._tagged_json(eq[r], "RESULT", r) for r in range(2)]
        wt_res = [self._tagged_json(wt[r], "RESULT", r) for r in range(2)]
        # world wall = the slowest rank's wall (the pass barrier)
        eq_wall = max(r["wall_s"] for r in eq_res)
        wt_wall = max(r["wall_s"] for r in wt_res)
        assert wt_wall < 0.75 * eq_wall, (
            f"weighted layout ({wt_wall:.2f}s) did not beat equal "
            f"({eq_wall:.2f}s) by the required margin"
        )
        # parity: same optimization, different reduction grouping
        c_eq = np.asarray(eq_res[0]["centers"])
        c_wt = np.asarray(wt_res[0]["centers"])
        assert np.max(np.abs(c_eq - c_wt)) <= 1e-5
        assert abs(eq_res[0]["cost"] - wt_res[0]["cost"]) <= 1e-3 * max(
            abs(eq_res[0]["cost"]), 1.0
        )
        # every rank computed the identical plan (rank-uniform contract)
        blocks = [self._tagged_json(wt[r], "BALANCE", r) for r in range(2)]
        assert blocks[0] == blocks[1]
        block = blocks[0]
        assert block["origin"] == "pinned"
        assert block["enabled"] is True
        extents = block["extents"]
        assert sum(r for _, r in extents) == 6000
        # rank 1 (capability 0.25) must hold the smaller extent
        assert extents[1][1] < extents[0][1]
        # fleet block shows assignment vs achievement side by side
        rows = self._tagged_json(wt[0], "FLEETROWS", 0)
        assert rows["per_rank_capability"] is not None
        assert rows["per_rank_rows"] is not None
        assert rows["per_rank_rows"][0] > rows["per_rank_rows"][1]

    def test_live_rebalance_shrinks_straggler_extent(self):
        """An initially-equal world (equal pinned capabilities) must
        detect the slowed rank from the fleet rollups and re-plan its
        extents mid-fit — the decision trail in summary.balance."""
        outs = self._launch_balance_world("rebalance")
        blocks = [self._tagged_json(outs[r], "BALANCE", r)
                  for r in range(2)]
        assert blocks[0] == blocks[1]  # identical decisions on every rank
        block = blocks[0]
        replans = block["replans"]
        assert replans, f"no replan recorded: {json.dumps(block)[:500]}"
        first = replans[0]
        assert first["slowest_rank"] == 1
        assert first["skew_ratio"] > 1.3
        # the re-planned extent moved rows OFF the straggler
        assert first["new_extents"][1][1] < first["old_extents"][1][1]
        final = block["extents"]
        assert final[1][1] < final[0][1]
        assert sum(r for _, r in final) == 6000
        # parity against the equal-shard oracle survives the re-plans
        eq = self._launch_balance_world("equal")
        c_eq = np.asarray(
            self._tagged_json(eq[0], "RESULT", 0)["centers"])
        c_rb = np.asarray(
            self._tagged_json(outs[0], "RESULT", 0)["centers"])
        assert np.max(np.abs(c_eq - c_rb)) <= 1e-5
