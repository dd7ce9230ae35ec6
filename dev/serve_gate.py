#!/usr/bin/env python
"""CI gate: the serving plane serves fast, exact, and compile-free.

Legs (ISSUE 13 acceptance):

1. **Parity** — registry-served results are bit-identical to direct
   model calls (K-Means/ALS ids + score bits) and <= 1e-6 (PCA) —
   served scoring must never drift from the model surface.
2. **Zero steady-state compiles** — after a bucket-family warmup, a
   50-request jittered-size storm compiles ZERO new XLA programs
   (ground truth via ``progcache.xla_compile_count``), with every
   answer matching the NumPy oracle.
3. **Full-sweep scale** — ``recommend_for_all_users`` over a 10M-user
   synthetic factor table completes with host memory bounded by
   output + O(chunk) (peak-RSS bound far under the quadratic score
   matrix), with exact parity on sampled rows.
4. **Sharded sweep** — the ring-merged factor-sharded sweep on the
   8-device pseudo-mesh exactly matches the single-device reference.
5. **Tail latency** — the request-storm microbench's p99 stays within
   bound of its p50 (no compile or upload spikes hiding in the tail).
6. **Disarmed seam** — the serving plane's only hook in the non-serving
   path (the identity-keyed device-pin check in model scoring) prices
   at <1% of the 20-predict microbench.
7. **Storm under eviction** (ISSUE 16) — a REAL 2-replica fleet runs a
   jittered storm through the async TrafficQueue while rank 1 is
   SIGKILLed mid-storm: the survivor must evict the fleet, keep the
   zero-steady-compile and p99-vs-p50 contracts in local-only mode,
   and shed loudly (one shed of each reason).  Hosts that cannot form
   a multiprocess jax world at all (the tests' _ENV_FAILURE_MARKERS
   signatures) WARN and skip the leg instead of failing the gate.
8. **Poison bisection** (ISSUE 18) — a NaN-payload request coalesced
   with innocents is isolated by log2 bisection: exactly one
   quarantine (``oap_serve_poison_total``), every innocent answered
   bit-identically, and ZERO new XLA compiles (the halves re-coalesce
   on the warmed bucket family).
9. **Graceful drain** (ISSUE 18) — ``TrafficQueue.drain`` answers
   every pending future, books ``oap_serve_drains_total`` exactly
   once, and the drained queue sheds new admissions with
   ``reason="draining"``.
10. **Brownout ladder** (ISSUE 18) — sustained 2x over-budget pressure
    walks the auto ladder exactly topk -> bf16 -> stale (3 steps
    booked), absorbing breaches at active rungs; the bf16 rung flips
    the serving precision policy only where a parity bound exists, and
    a pinned rung halves top-k depth.
11. **Request-lifecycle chaos drill** (ISSUE 18) — a REAL 2-replica
    fleet under a 220-request storm with armed ``serve.dispatch``
    transients, an injected ``serve.batch`` poison, real NaN-payload
    requests, and rank 1 SIGKILLed mid-storm: the survivor resolves
    EVERY accepted future (answered bit-identically or classified),
    quarantines exactly the poison payloads, retries the transients,
    compiles nothing in steady state, then re-forms the sharded sweep
    on its local layout with bit-identical answers.

Exit 1 with the offending numbers on any violation.
"""

from __future__ import annotations

import os
import resource
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

failures = []


def check(ok, msg):
    if not ok:
        failures.append(msg)
        print(f"FAIL: {msg}")


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

    from oap_mllib_tpu import serving
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.fallback.kmeans_np import predict_np
    from oap_mllib_tpu.models.als import ALS, ALSModel
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.models.pca import PCA
    from oap_mllib_tpu.serving import sweep as sweep_mod
    from oap_mllib_tpu.utils import progcache

    rng = np.random.default_rng(11)

    # -- leg 1: served vs direct parity --------------------------------------
    print("== serve gate: served-vs-direct parity (3 estimators) ==")
    x = rng.normal(size=(500, 16)).astype(np.float32)
    km = KMeans(k=6, seed=3, max_iter=4).fit(x)
    hk = serving.serve(km)
    check(np.array_equal(hk.predict(x[:123]), km.predict(x[:123])),
          "served K-Means ids != direct predict")

    pca = PCA(k=4).fit(x)
    hp = serving.serve(pca)
    dev = np.abs(hp.transform(x[:77]) - pca.transform(x[:77])).max()
    check(dev <= 1e-6, f"served PCA projection deviates {dev:.2e}")

    u = rng.integers(0, 80, size=4000)
    i = rng.integers(0, 64, size=4000)
    r = rng.normal(size=4000).astype(np.float32)
    als = ALS(rank=5, max_iter=2, seed=1).fit(u, i, r, n_users=80,
                                              n_items=64)
    ha = serving.serve(als)
    ids_m, s_m = als.recommend_for_all_users(7, with_scores=True)
    ids_h, s_h = ha.recommend_for_all_users(7, with_scores=True)
    check(np.array_equal(ids_m, ids_h), "served ALS sweep ids != model")
    check(np.array_equal(s_m, s_h), "served ALS sweep scores != model bits")

    # -- leg 2: zero steady-state compiles under a jittered storm ------------
    print("== serve gate: 50-request jittered-size storm, zero XLA "
          "compiles after warmup ==")
    storm_x = rng.normal(size=(1024, 16)).astype(np.float32)
    hk.warmup(1024)
    oracle_centers = km.cluster_centers_.astype(np.float64)
    before = progcache.xla_compile_count()
    for s in rng.integers(1, 1024, size=50):
        s = int(s)
        ids = hk.predict(storm_x[:s])
        expect = predict_np(
            storm_x[:s].astype(np.float64), oracle_centers, "euclidean"
        )
        if not np.array_equal(ids, expect):
            check(False, f"storm answer diverged at size {s}")
            break
    storm_compiles = progcache.xla_compile_count() - before
    print(f"  storm XLA compiles: {storm_compiles}")
    check(storm_compiles == 0,
          f"jittered storm compiled {storm_compiles} new XLA programs "
          "(steady state must be 0)")

    # -- leg 3: 10M-user full sweep, bounded host memory ---------------------
    big = int(os.environ.get("SERVE_GATE_SWEEP_USERS", 10_000_000))
    print(f"== serve gate: {big:,}-user full-sweep top-k "
          "(streamed + prefetched, no quadratic score matrix) ==")
    nu, ni, rk, topk = big, 64, 4, 2
    uf = rng.normal(size=(nu, rk)).astype(np.float32)
    itf = rng.normal(size=(ni, rk)).astype(np.float32)
    big_model = ALSModel(uf, itf)
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    t0 = time.perf_counter()
    ids = sweep_mod.recommend_for_all_users(big_model, topk)
    wall = time.perf_counter() - t0
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    grew_mb = max(0, rss1 - rss0) / 1024.0
    print(f"  {nu:,} users in {wall:.1f}s "
          f"({nu / wall / 1e6:.2f}M users/sec), peak-RSS growth "
          f"{grew_mb:.0f} MB")
    check(ids.shape == (nu, topk), f"sweep shape {ids.shape}")
    # quadratic scores would be nu x ni x 4 B (2.4 GB at 10M x 64);
    # the streamed sweep's growth is output + chunks — bound well under
    quad_mb = nu * ni * 4 / 1024 / 1024
    bound_mb = 0.5 * quad_mb
    check(grew_mb < bound_mb,
          f"sweep grew RSS {grew_mb:.0f} MB (>= {bound_mb:.0f} MB — "
          "the quadratic score matrix may be materializing)")
    sample = rng.integers(0, nu, size=32)
    expect = np.argsort(-(uf[sample] @ itf.T), axis=1,
                        kind="stable")[:, :topk]
    check(np.array_equal(ids[sample], expect),
          "10M sweep sampled rows diverge from the direct top-k")
    del uf, itf, big_model, ids

    # -- leg 4: factor-sharded ring sweep on the 8-device pseudo-mesh --------
    print("== serve gate: ring-merged sharded sweep parity "
          "(8-device pseudo-mesh) ==")
    set_config(als_item_layout="sharded")
    m_sh = ALS(rank=6, max_iter=2, seed=2).fit(
        rng.integers(0, 200, size=6000), rng.integers(0, 96, size=6000),
        rng.normal(size=6000).astype(np.float32),
        n_users=200, n_items=96,
    )
    set_config(als_item_layout="auto")
    check(m_sh._sharded_user is not None and m_sh._sharded_item is not None,
          "sharded fixture did not produce a block-sharded model")
    ids_sh, s_sh = sweep_mod.recommend_for_all_users(
        m_sh, 7, with_scores=True
    )
    ref = ALSModel(np.array(m_sh.user_factors_),
                   np.array(m_sh.item_factors_))
    ids_ref, s_ref = ref._top_k_scores(ref.user_factors_,
                                       ref.item_factors_, 7)
    check(np.array_equal(ids_sh, ids_ref),
          "sharded ring sweep ids != single-device reference")
    check(np.array_equal(s_sh, s_ref),
          "sharded ring sweep score bits != single-device reference")

    # -- leg 5: tail latency bound on the request-storm microbench -----------
    print("== serve gate: p99-vs-p50 tail bound on the storm microbench ==")
    import bench

    res = bench.bench_serving(requests=100, sweep_users=100_000,
                              emit=False)
    p50, p99 = res["p50_s"], res["p99_s"]
    print(f"  p50 {p50 * 1e3:.2f} ms, p99 {p99 * 1e3:.2f} ms, "
          f"qps {res['qps']:.0f}")
    check(res["steady_compiles"] == 0,
          f"microbench storm compiled {res['steady_compiles']} programs")
    # generous CI-noise bound: a compile or re-upload hiding in the
    # tail costs 100x+, scheduler jitter does not
    check(p99 <= max(50.0 * p50, 0.25),
          f"p99 {p99 * 1e3:.1f} ms breaches the tail bound "
          f"(p50 {p50 * 1e3:.1f} ms)")

    # -- leg 6: disarmed seam — the pin check prices at ~0 -------------------
    print("== serve gate: device-pin seam cost vs the 20-predict "
          "microbench ==")
    from oap_mllib_tpu.serving.registry import pin

    xs = rng.normal(size=(256, 16)).astype(np.float32)
    km.predict(xs)  # warm
    t0 = time.perf_counter()
    for _ in range(20):
        km.predict(xs)
    predict_wall = time.perf_counter() - t0
    cache = km._dev_cache
    reps = 2000
    t0 = time.perf_counter()
    for _ in range(reps):
        for _ in range(100):  # 100 seam touches per predict: a large
            pin(cache, "centers", km.cluster_centers_)  # overestimate
    seam_wall = (time.perf_counter() - t0) * (20.0 / reps)
    pct = 100.0 * seam_wall / predict_wall
    print(f"  20-predict wall {predict_wall * 1e3:.1f} ms; seam cost "
          f"{seam_wall * 1e3:.3f} ms (~{pct:.2f}%)")
    check(seam_wall < max(0.01 * predict_wall, 0.005),
          f"pin seam cost measurable: {seam_wall:.4f}s vs "
          f"{predict_wall:.4f}s predict wall")

    # -- leg 7: storm under eviction on a REAL 2-replica fleet ---------------
    print("== serve gate: traffic-plane storm under replica eviction "
          "(2-process fleet) ==")
    _traffic_eviction_leg()

    # -- leg 8: poison-batch bisection, zero compiles ------------------------
    print("== serve gate: poison-batch bisection (quarantine + "
          "innocents + zero compiles) ==")
    from oap_mllib_tpu.serving import traffic as traffic_mod
    from oap_mllib_tpu.telemetry import metrics as tm

    traffic_mod._reset_for_tests()
    poison0 = int(tm.family_total("oap_serve_poison_total"))
    bisect0 = int(tm.family_total("oap_serve_bisect_total"))
    compiles0 = progcache.xla_compile_count()
    q8 = serving.TrafficQueue(hk, start=False)
    innocents = [storm_x[:5], storm_x[5:17], storm_x[17:47]]
    bad = np.full((7, 16), np.nan, np.float32)
    futs8 = [q8.submit(b) for b in innocents]
    fp8 = q8.submit(bad)
    q8.pump()
    q8.close()
    check(progcache.xla_compile_count() - compiles0 == 0,
          "bisection halves compiled new programs (bucket family "
          "must stay warm)")
    poison_n = int(tm.family_total("oap_serve_poison_total")) - poison0
    check(poison_n == 1, f"expected exactly 1 quarantine, got {poison_n}")
    check(int(tm.family_total("oap_serve_bisect_total")) - bisect0 >= 1,
          "poison batch was never bisected")
    exc8 = fp8.exception()
    check(isinstance(exc8, serving.ServeError)
          and exc8.reason == "poison",
          f"poison request not quarantined: {exc8!r}")
    for b, f in zip(innocents, futs8):
        if not np.array_equal(f.result(), hk.predict(b)):
            check(False, "innocent sharing the poisoned flush diverged")
            break
    print(f"  quarantined 1 of {len(innocents) + 1} coalesced requests, "
          f"0 compiles")

    # -- leg 9: graceful drain -----------------------------------------------
    print("== serve gate: graceful drain flushes every future, then "
          "sheds admissions ==")
    drains0 = int(tm.family_total("oap_serve_drains_total"))
    q9 = serving.TrafficQueue(hk, start=False)
    futs9 = [q9.submit(storm_x[:9]) for _ in range(5)]
    stats9 = q9.drain(timeout_s=5.0)
    check(stats9["drained"] and stats9["failed"] == 0,
          f"drain left failures: {stats9}")
    check(stats9["answered"] == 5,
          f"drain answered {stats9['answered']}/5 pending futures")
    check(all(f.exception() is None for f in futs9),
          "drained futures did not all answer")
    check(int(tm.family_total("oap_serve_drains_total")) - drains0 == 1,
          "oap_serve_drains_total not booked exactly once")
    try:
        q9.submit(storm_x[:3])
        check(False, "drained queue admitted a new request")
    except serving.ShedError as e:
        check(e.reason == "draining",
              f"post-drain shed reason {e.reason!r} != 'draining'")
    q9.close()
    print(f"  drained {stats9['answered']} futures, admissions shed")

    # -- leg 10: brownout ladder ---------------------------------------------
    print("== serve gate: brownout ladder steps topk -> bf16 -> stale "
          "under sustained pressure ==")
    from oap_mllib_tpu.serving import batcher as batcher_mod

    steps0 = int(tm.family_total("oap_serve_brownout_steps_total"))
    absorbed0 = int(tm.family_total("oap_serve_brownout_absorbed_total"))
    b10 = serving.BrownoutController("auto")
    for _ in range(12):
        b10.observe(200, 100)  # sustained 2x over-budget
    check(b10.rung == 3,
          f"ladder stopped at rung {b10.rung} (expected 3/stale)")
    check([s["to"] for s in b10.steps] == ["topk", "bf16", "stale"],
          f"ladder walked {[s['to'] for s in b10.steps]}")
    check(int(tm.family_total("oap_serve_brownout_steps_total"))
          - steps0 == 3, "expected exactly 3 brownout steps booked")
    check(int(tm.family_total("oap_serve_brownout_absorbed_total"))
          - absorbed0 >= 1, "no breach was absorbed at an active rung")
    set_config(serve_brownout="pin:bf16")
    traffic_mod._reset_for_tests()
    pol10 = batcher_mod.resolve_policy("kmeans").name
    check(pol10 == "bf16",
          f"bf16 rung did not flip serving precision (got {pol10!r})")
    set_config(serve_brownout="pin:topk")
    traffic_mod._reset_for_tests()
    check(serving.brownout_topk(8) == 4,
          "topk rung did not halve the sweep depth")
    set_config(serve_brownout="auto")
    traffic_mod._reset_for_tests()
    print("  ladder: topk -> bf16 -> stale, precision + depth rungs "
          "verified")

    # -- leg 11: request-lifecycle chaos drill (2-process fleet) -------------
    print("== serve gate: request-lifecycle chaos drill (retries + "
          "poison + SIGKILL on a 2-process fleet) ==")
    _traffic_drill_leg()

    if failures:
        print(f"\nserve gate: {len(failures)} failure(s)")
        return 1
    print("\nserve gate: OK")
    return 0


# environment-incapability signatures (mirrors the pseudo-cluster
# suite): a worker that died on one of these means this HOST cannot
# form a multiprocess jax world — warn + skip, not a gate failure
_ENV_FAILURE_MARKERS = (
    "Multiprocess computations aren't implemented",
    "UNIMPLEMENTED",
    "Unable to initialize backend",
    "failed to join world",
    "DEADLINE_EXCEEDED",
    "Failed to connect to coordinator",
)


def _spawn_traffic_world(mode, nproc, crash_dir, timeout=180,
                         env_extra=None):
    """Spawn an nproc traffic-worker world and return (procs, outs),
    or None when the host cannot form a multiprocess jax world (the
    WARN-skip path).  Workers pick their own device count, so the
    gate's 8-device forcing is stripped from their environment."""
    import subprocess

    from oap_mllib_tpu.parallel.bootstrap import free_port

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "pseudo_cluster_worker_traffic.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["TRAFFIC_WORKER_MODE"] = mode
    env["TRAFFIC_CRASH_DIR"] = crash_dir
    env.update(env_extra or {})
    coord = f"127.0.0.1:{free_port('127.0.0.1', 4000)}"
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(r), str(nproc), coord, "1"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=repo,
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
    for out in outs:
        if any(m in out for m in _ENV_FAILURE_MARKERS):
            print("  WARN: this host cannot form a multiprocess jax "
                  "world; skipping the leg (not a gate failure)")
            return None
    return procs, outs


def _traffic_fields(out, tag):
    line = [ln for ln in out.splitlines() if ln.startswith(tag + " ")]
    if not line:
        return None
    return dict(p.split("=", 1) for p in line[-1].split()[1:])


def _traffic_eviction_leg():
    import tempfile

    with tempfile.TemporaryDirectory() as crash_dir:
        spawned = _spawn_traffic_world("evict", 2, crash_dir)
        if spawned is None:
            return
        procs, outs = spawned
        # rank 1 genuinely preempted mid-storm; rank 0 survived
        check(procs[1].returncode == -9,
              f"victim replica was not SIGKILLed:\n{outs[1][-1500:]}")
        check(procs[0].returncode == 0,
              f"survivor replica failed:\n{outs[0][-1500:]}")
        check("EVICTED rank=0" in outs[0],
              "survivor never evicted the dead replica")
        storm = _traffic_fields(outs[0], "STORM_OK rank=0")
        check(storm is not None, "survivor never finished the storm")
        if storm is not None:
            print(f"  survivor storm: p50 {storm['p50_ms']} ms, "
                  f"p99 {storm['p99_ms']} ms, "
                  f"compiles {storm['compiles']}")
            check(storm["compiles"] == "0",
                  f"storm under eviction compiled {storm['compiles']} "
                  "programs (steady state must be 0)")
            check(storm["local_only"] == "True",
                  "survivor did not flip to local-only mode")
            p50, p99 = float(storm["p50_ms"]), float(storm["p99_ms"])
            # same bound as leg 5, in ms
            check(p99 <= max(50.0 * p50, 250.0),
                  f"eviction-storm p99 {p99:.1f} ms breaches the tail "
                  f"bound (p50 {p50:.1f} ms)")
        check("SHED_OK rank=0 sheds=3" in outs[0],
              "survivor's shed legs incomplete (expected one shed of "
              "each reason: queue_full, budget, deadline)")


def _traffic_drill_leg():
    import tempfile

    with tempfile.TemporaryDirectory() as crash_dir:
        spawned = _spawn_traffic_world("drill", 2, crash_dir, timeout=300)
        if spawned is None:
            return
        procs, outs = spawned
        check(procs[1].returncode == -9,
              f"victim replica was not SIGKILLed:\n{outs[1][-1500:]}")
        check(procs[0].returncode == 0,
              f"survivor replica failed the drill:\n{outs[0][-1500:]}")
        check("EVICTED rank=0" in outs[0],
              "survivor never evicted the dead replica")
        drill = _traffic_fields(outs[0], "DRILL_OK rank=0")
        check(drill is not None,
              f"survivor never finished the drill:\n{outs[0][-1500:]}")
        if drill is not None:
            print(f"  drill: submitted {drill['submitted']}, answered "
                  f"{drill['answered']}, poison {drill['poison']}, "
                  f"retried {drill['retried']}, bisects "
                  f"{drill['bisects']}, compiles {drill['compiles']}")
            check(int(drill["submitted"]) >= 200,
                  f"drill storm too small: {drill['submitted']} < 200")
            check(drill["unresolved"] == "0",
                  f"{drill['unresolved']} accepted futures never "
                  "resolved (silent loss)")
            check(drill["poison"] == "3",
                  f"expected exactly 3 quarantines, got {drill['poison']}")
            check(int(drill["retried"]) >= 1,
                  "dispatcher transients were never retried")
            check(int(drill["bisects"]) >= 1,
                  "poison batches were never bisected")
            check(drill["compiles"] == "0",
                  f"drill compiled {drill['compiles']} programs in "
                  "steady state (must be 0)")
        reform = _traffic_fields(outs[0], "REFORM_OK rank=0")
        check(reform is not None,
              "survivor never re-formed the sharded sweep on its "
              "local layout")
        if reform is not None:
            check(int(reform["reforms"]) >= 1,
                  "oap_serve_sweep_reforms_total was never booked")
            print(f"  re-formed sweep: {reform['reforms']} reform(s), "
                  f"digest {reform['digest']}")


if __name__ == "__main__":
    sys.exit(main())
