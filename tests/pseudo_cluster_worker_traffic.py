"""Traffic-plane pseudo-cluster worker (ISSUE 16).

One replica of a REAL ``jax.distributed`` serving fleet driving the
async traffic plane end to end:

1. **Sharded-sweep parity** — shard deterministic ALS factor tables
   onto the live multi-process mesh (``sweep.shard_factors`` — the
   elastic redistribution pass), run the ring-rotated factor-sharded
   full sweep, and assert IN-PROCESS that ids AND score bits match the
   single-process reference (``ALSModel._top_k_scores``).  Prints
   ``PARITY_OK`` + a digest the parent cross-checks across ranks.
2. **Jittered storm** — waves of jittered-size requests through a
   :class:`serving.TrafficQueue` (submit -> future -> result walls),
   fleet heartbeats between waves over the deadline-watchdogged host
   collective plane, and a zero-steady-state-compile assertion from the
   XLA ground truth.  Prints ``STORM_OK rank= reqs= p50_ms= p99_ms=
   compiles=``.
3. **Loud shedding** (rank 0) — synthetic tight knobs drive one shed of
   each reason (queue_full / budget / deadline) with zero OOM.  Prints
   ``SHED_OK sheds=3``.

Modes (env ``TRAFFIC_WORKER_MODE``):

- ``healthy`` — every rank runs all legs and exits 0.
- ``evict`` — rank 1 SIGKILLs itself at the start of storm wave 1 (a
  preempted replica); rank 0's next heartbeat converts into a
  ``CollectiveTimeoutError`` which the :class:`ReplicaGuard` absorbs:
  the survivor prints ``EVICTED``, keeps answering the remaining waves
  in local-only mode, and still holds the p99 and zero-compile
  contracts.
- ``bench`` — the ``serving_kmeans_qps_mp`` headline: a sustained
  storm through the async queue, printing ``BENCH_QPS rank=0 qps=
  p50_ms= p99_ms=`` for bench.py to parse.
- ``trace`` — the ISSUE 19 observability world: request tracing
  (``serve_trace_sample=1.0``) + the SLO engine + the flight recorder
  + the JSONL telemetry sink armed BEFORE the leg-1 sharded sweep, so
  its ring-hop rotations and a traced storm's request ledgers land in
  per-rank sinks (``$TRAFFIC_TRACE_SINK.rank<r>``) that the parent
  merges through ``dev/oaptrace.py``.  Every answered future must
  carry a finalized ledger whose stages sum to its wall within 5%.
  Prints ``TRACE_OK rank= reqs= missing= bad_cov= sampled=``.
- ``drill`` — the ISSUE 18 request-lifecycle chaos drill: a >=200
  request storm with armed ``serve.dispatch`` transient faults (the
  retry envelope), an injected ``serve.batch`` poison plus real
  NaN-payload requests at known indices (bisection + quarantine),
  and rank 1 SIGKILLed mid-storm (eviction).  The survivor must
  resolve EVERY accepted future — answered bit-identically to direct
  ``handle.predict`` or failed with a classified ``ServeError`` —
  with zero steady-state compiles, print ``DRILL_OK`` with the exact
  counters, then re-form the leg-1 sharded sweep on its local layout
  (``shard_factors_local``) and prove bit-identical answers
  (``REFORM_OK``).

Invoked as:  python pseudo_cluster_worker_traffic.py RANK NPROC COORD LOCAL_DEV
(the standard worker argv — the shared _launch_world plumbing spawns it).
"""

import hashlib
import os
import sys
import time

rank, nproc = int(sys.argv[1]), int(sys.argv[2])
coord, local_dev = sys.argv[3], int(sys.argv[4])
mode = os.environ["TRAFFIC_WORKER_MODE"]
crash_dir = os.environ["TRAFFIC_CRASH_DIR"]

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", local_dev)

import numpy as np

if nproc > 1:
    from oap_mllib_tpu.parallel import bootstrap

    ran = bootstrap.initialize_distributed(coord, nproc, rank)
    assert ran, "initialize_distributed returned False"

from oap_mllib_tpu import serving
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.models.kmeans import KMeans
from oap_mllib_tpu.utils import progcache

# the heartbeat deadline is the eviction mechanism under test: well
# under the parent's watchdog, well over a healthy heartbeat
set_config(collective_timeout=10.0, crash_dir=crash_dir)


def _exit_barrier(tag, wait=True):
    # collective-free exit barrier: the first replica to _exit would
    # tear down the coordination service under its still-working
    # peers — wait until every rank has filed its done marker.  Rank 0
    # HOSTS the coordination service, so it must exit last: a peer
    # still in its poll sleep when the leader dies gets a fatal
    # "leader task died" abort from the error-polling thread.
    open(os.path.join(crash_dir, f"{tag}.done.rank{rank}"), "w").close()
    if wait:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not all(
            os.path.exists(os.path.join(crash_dir, f"{tag}.done.rank{r}"))
            for r in range(nproc)
        ):
            time.sleep(0.05)
        if rank == 0 and nproc > 1:
            time.sleep(1.0)
    os._exit(0)

if mode == "trace":
    # arm the whole observability plane BEFORE the leg-1 sharded sweep
    # so its ring-hop rotations land in the flight recorder, and tag
    # this process's rank so trace ids and sink files are per-rank
    set_config(
        process_id=rank,
        num_processes=nproc,
        flight_recorder=4096,
        telemetry_log=os.environ["TRAFFIC_TRACE_SINK"],
        serve_trace_sample=1.0,
        serve_slo_p99_ms=float(os.environ.get("TRAFFIC_SLO_P99_MS", "500")),
    )

# hosts whose jax build forms worlds but cannot RUN multiprocess
# computations (the pseudo-cluster CPU backend) die inside the sharded
# sweep with one of these — trace mode degrades to a collective-free
# traced storm there instead of losing the whole leg
_SHARDED_UNSUPPORTED = (
    "Multiprocess computations aren't implemented",
    "UNIMPLEMENTED",
)

# -- leg 1: multi-process sharded sweep, bit-identical to the reference
sweep_ok = True
if mode != "bench":
    from oap_mllib_tpu.models.als import ALSModel
    from oap_mllib_tpu.parallel.mesh import get_mesh
    from oap_mllib_tpu.serving import sweep

    try:
        prng = np.random.default_rng(123)
        uf = prng.normal(size=(96, 5)).astype(np.float32)
        itf = prng.normal(size=(64, 5)).astype(np.float32)
        mesh = get_mesh()
        ub, uoff, upp = sweep.shard_factors(uf, mesh)
        ib, ioff, ipp = sweep.shard_factors(itf, mesh)
        sharded = ALSModel(
            None, None,
            sharded_user=(ub, uoff, upp), sharded_item=(ib, ioff, ipp),
        )
        ids, scores = sweep.recommend_for_all_users(
            sharded, 8, with_scores=True)
        ref = ALSModel(uf, itf)
        ids_ref, s_ref = ref._top_k_scores(uf, itf, 8)
        assert np.array_equal(ids, ids_ref), "sharded sweep ids diverge"
        # the same dot products compiled for a 2-process mesh and for
        # one device agree to the last ulp or so, not bit for bit
        assert np.allclose(scores, s_ref, rtol=1e-6, atol=0.0), \
            "sharded sweep scores diverge"
        digest = hashlib.sha256(
            ids.tobytes() + scores.tobytes()).hexdigest()[:16]
        print(f"PARITY_OK rank={rank} digest={digest}", flush=True)
    except Exception as e:
        if mode == "trace" and any(
            m in repr(e) for m in _SHARDED_UNSUPPORTED
        ):
            sweep_ok = False
            print(f"SWEEP_SKIP rank={rank}", flush=True)
        else:
            raise

# -- serve one replicated model per replica (the fleet contract)
rng = np.random.default_rng(77)
if mode == "bench" or (mode == "trace" and not sweep_ok):
    # the QPS headline prices SERVING, not fitting: identical synthetic
    # centers on every replica (no collective — the leg runs even on
    # hosts whose jax build cannot fit across processes).  A
    # sweep-skipped trace world takes the same path: the tracing plane
    # prices requests, not the fit that made the model.
    from oap_mllib_tpu.models.kmeans import KMeansModel

    model = KMeansModel(rng.normal(size=(4, 8)).astype(np.float32))
else:
    x = rng.normal(size=(600, 8)).astype(np.float32)
    model = KMeans(k=4, seed=5, init_mode="random", max_iter=4).fit(x)
handle = serving.serve(model)
handle.warmup(128)

if mode == "bench":
    n_req = int(os.environ.get("TRAFFIC_BENCH_REQUESTS", "200"))
    reqs = [
        rng.normal(size=(int(s), 8)).astype(np.float32)
        for s in rng.integers(5, 128, size=n_req)
    ]
    with serving.TrafficQueue(handle) as q:
        for b in reqs[:16]:  # warm wave: async path + buckets hot
            q.submit(b, deadline_ms=60_000).result(timeout=60)
        t0 = time.perf_counter()
        subs = [
            (time.perf_counter(), q.submit(b, deadline_ms=120_000))
            for b in reqs
        ]
        walls = []
        for ts, f in subs:
            f.result(timeout=120)
            walls.append(time.perf_counter() - ts)
        total = time.perf_counter() - t0
    walls.sort()
    p50 = walls[len(walls) // 2]
    p99 = walls[min(len(walls) - 1, int(len(walls) * 0.99))]
    print(
        f"BENCH_QPS rank={rank} qps={n_req / total:.1f} "
        f"p50_ms={p50 * 1e3:.3f} p99_ms={p99 * 1e3:.3f}",
        flush=True,
    )
    _exit_barrier("bench")

# -- drill mode: durable futures under replica death + poison + retries
if mode == "drill":
    from oap_mllib_tpu.telemetry import metrics as _tm

    set_config(serve_queue_depth=1024, serve_retry_limit=3,
               serve_retry_backoff=0.005)
    guard = serving.ReplicaGuard()
    q = serving.TrafficQueue(handle)
    # warm wave: async path, bucket family, and the heartbeat shapes
    # all hot BEFORE the chaos arms — the zero-compile clock starts
    # here.  Coalesced flushes bucket on the SUM of request rows (the
    # 1024-row flush bound), so the family warms to that bound, not
    # just the largest single request.
    handle.warmup(1024)
    for b in [
        rng.normal(size=(int(s), 8)).astype(np.float32)
        for s in rng.integers(5, 128, size=12)
    ]:
        q.submit(b, deadline_ms=120_000).result(timeout=120)
    with guard.leg():
        if nproc > 1:
            serving.heartbeat(requests=handle.requests,
                              queue_depth=q.depth())
    compile_snap = progcache.xla_compile_count()
    # the storm: two transient dispatcher faults (retry envelope), one
    # injected coalesced-batch poison (bisection with innocents), and
    # three REAL NaN-payload requests at known indices (data poison the
    # finite-guard quarantines deterministically)
    set_config(fault_spec="serve.dispatch:fail=2,serve.batch:nan=1")
    n_req = 220
    per_wave = n_req // 5
    poison_at = {31, 97, 171}
    reqs = []
    for i, s in enumerate(rng.integers(5, 128, size=n_req)):
        b = rng.normal(size=(int(s), 8)).astype(np.float32)
        if i in poison_at:
            b[0, 0] = np.nan
        reqs.append(b)
    futs = {}
    announced = False
    for w in range(5):
        if rank == 1 and nproc > 1 and w == 1:
            import signal

            os.kill(os.getpid(), signal.SIGKILL)  # a preempted replica
        wave = range(w * per_wave, (w + 1) * per_wave)
        with guard.leg():
            for i in wave:
                futs[i] = q.submit(reqs[i], deadline_ms=120_000)
            for i in wave:
                try:
                    futs[i].result(timeout=120)
                except Exception:
                    pass  # classified failures audited below
            if not guard.local_only and nproc > 1:
                serving.heartbeat(requests=handle.requests,
                                  queue_depth=q.depth())
        if guard.local_only and not announced:
            announced = True
            err = type(guard.last_error).__name__
            print(f"EVICTED rank={rank} wave={w} err={err}", flush=True)
    steady_compiles = progcache.xla_compile_count() - compile_snap
    q.close()
    # the request-lifecycle audit: EVERY accepted future resolved —
    # exactly the poison requests quarantined, everything else answered
    # bit-identically to a direct predict on the same handle
    unresolved = sum(1 for f in futs.values() if not f.done())
    assert unresolved == 0, f"{unresolved} futures leaked"
    poison, answered = [], 0
    for i, f in sorted(futs.items()):
        exc = f.exception()
        if exc is None:
            answered += 1
            assert np.array_equal(f.result(), handle.predict(reqs[i])), (
                f"req {i}: async answer diverges from direct predict"
            )
        else:
            assert isinstance(exc, serving.ServeError), (
                f"req {i}: unclassified failure {exc!r}"
            )
            assert exc.reason == "poison", f"req {i}: {exc.reason}"
            poison.append(i)
    assert set(poison) == poison_at, (poison, poison_at)
    retried = int(_tm.family_total("oap_serve_retries_total"))
    bisects = int(_tm.family_total("oap_serve_bisect_total"))
    assert retried >= 1, "dispatcher transients never retried"
    assert bisects >= 1, "poison batches never bisected"
    print(
        f"DRILL_OK rank={rank} submitted={n_req} answered={answered} "
        f"poison={len(poison)} retried={retried} bisects={bisects} "
        f"unresolved={unresolved} compiles={steady_compiles}",
        flush=True,
    )
    # -- re-form the leg-1 sharded sweep on the survivor's live layout:
    # the old mesh spans the dead rank, so the sweep must refuse it
    # (classified, pre-launch) and the reform hook re-shards the host
    # tables across LOCAL devices — answers stay bit-identical
    if rank == 0 and nproc > 1:
        assert serving.fleet_evicted(), "drill requires an eviction"
        ids2, s2 = sweep.recommend_for_all_users(
            sharded, 8, with_scores=True,
            reform=lambda exc: ALSModel(
                None, None,
                sharded_user=sweep.shard_factors_local(uf),
                sharded_item=sweep.shard_factors_local(itf),
            ),
        )
        assert np.array_equal(ids2, ids_ref), "re-formed sweep ids diverge"
        assert np.array_equal(s2, s_ref), "re-formed sweep score bits diverge"
        reforms = int(_tm.family_total("oap_serve_sweep_reforms_total"))
        rdigest = hashlib.sha256(
            ids2.tobytes() + s2.tobytes()
        ).hexdigest()[:16]
        print(f"REFORM_OK rank={rank} reforms={reforms} digest={rdigest}",
              flush=True)
    open(os.path.join(crash_dir, f"traffic.done.rank{rank}"), "w").close()
    os._exit(0)

# -- trace mode: a traced storm on top of the leg-1 sharded sweep; the
# per-rank JSONL sinks are the parent gate's oaptrace input
if mode == "trace":
    from oap_mllib_tpu.serving import reqtrace
    from oap_mllib_tpu.telemetry import export

    handle.warmup(1024)
    guard = serving.ReplicaGuard()
    with guard.leg():
        if nproc > 1 and sweep_ok:
            # one heartbeat = one collective flightrec event per rank —
            # the clock-alignment anchor oaptrace merges the sinks on
            # (collectives proven live by leg 1; a sweep-skipped host
            # would die here the same way)
            serving.heartbeat(requests=handle.requests)
    n_req = int(os.environ.get("TRAFFIC_TRACE_REQUESTS", "40"))
    reqs = [
        rng.normal(size=(int(s), 8)).astype(np.float32)
        for s in rng.integers(5, 128, size=n_req)
    ]
    with serving.TrafficQueue(handle) as q:
        futs = [q.submit(b, deadline_ms=120_000) for b in reqs]
        for f in futs:
            f.result(timeout=120)
    ledgers = [reqtrace.ledger_of(f) for f in futs]
    missing = sum(1 for lg in ledgers if lg is None or not lg.outcome)
    bad_cov = sum(
        1 for lg in ledgers
        if lg is not None and lg.wall_s > 1e-6
        and abs(lg.stage_sum() - lg.wall_s) > 0.05 * lg.wall_s
    )
    sampled = sum(
        1 for lg in ledgers if lg is not None and lg.ctx.sampled
    )
    # os._exit skips atexit: drain the flight recorder + final metrics
    # snapshot into the sink NOW so the parent's merge sees the ring
    # hops and request records
    export.shutdown()
    print(
        f"TRACE_OK rank={rank} reqs={n_req} missing={missing} "
        f"bad_cov={bad_cov} sampled={sampled} sweep={int(sweep_ok)}",
        flush=True,
    )
    _exit_barrier("trace")

# -- leg 2: jittered storm, heartbeats between waves, zero steady compiles
waves = [
    [
        rng.normal(size=(int(s), 8)).astype(np.float32)
        for s in rng.integers(5, 128, size=12)
    ]
    for _ in range(3)
]
guard = serving.ReplicaGuard()
walls = []
announced = False
compile_snap = None
q = serving.TrafficQueue(handle)
for w, wave in enumerate(waves):
    if mode == "evict" and rank == 1 and nproc > 1 and w == 1:
        import signal

        os.kill(os.getpid(), signal.SIGKILL)  # a preempted replica
    with guard.leg():
        futs = [
            (time.perf_counter(), q.submit(b, deadline_ms=120_000))
            for b in wave
        ]
        for ts, f in futs:
            f.result(timeout=120)
            walls.append(time.perf_counter() - ts)
        if not guard.local_only and nproc > 1:
            view = serving.heartbeat(
                requests=handle.requests, queue_depth=q.depth()
            )
            if w == 0:
                print(f"FLEET rank={rank} world={view['world']}", flush=True)
    if guard.local_only and not announced:
        announced = True
        err = type(guard.last_error).__name__
        print(f"EVICTED rank={rank} wave={w} err={err}", flush=True)
    if w == 0:
        # wave 0 is the warm wave (first heartbeat shapes included);
        # everything after must compile NOTHING, and the latency
        # contract (p99 vs p50) is judged on steady-state waves only
        compile_snap = progcache.xla_compile_count()
        walls = []
q.close()
steady_compiles = progcache.xla_compile_count() - compile_snap
walls.sort()
p50 = walls[len(walls) // 2]
p99 = walls[min(len(walls) - 1, int(len(walls) * 0.99))]
print(
    f"STORM_OK rank={rank} reqs={len(walls)} p50_ms={p50 * 1e3:.3f} "
    f"p99_ms={p99 * 1e3:.3f} compiles={steady_compiles} "
    f"local_only={guard.local_only}",
    flush=True,
)

# -- leg 3 (rank 0): one loud shed of each reason, zero OOM
if rank == 0:
    sheds = []
    set_config(serve_queue_depth=1)
    q2 = serving.TrafficQueue(handle, start=False)
    held = q2.submit(waves[0][0])
    try:
        q2.submit(waves[0][1])
    except serving.ShedError as e:
        sheds.append(e.reason)
    set_config(serve_queue_depth=256, memory_budget_hbm="2K",
               serve_shed_headroom=0.5)
    try:
        q2.submit(np.zeros((512, 8), np.float32))
    except serving.ShedError as e:
        sheds.append(e.reason)
    set_config(memory_budget_hbm="")
    late = q2.submit(waves[0][2], deadline_ms=1.0)
    time.sleep(0.05)
    q2.pump()
    if isinstance(late.exception(), serving.ShedError):
        sheds.append(late.exception().reason)
    assert held.result(timeout=30) is not None  # admitted work still answers
    q2.close()
    assert sheds == ["queue_full", "budget", "deadline"], sheds
    print(f"SHED_OK rank={rank} sheds={len(sheds)}", flush=True)

print(
    f"TRAFFIC_OK rank={rank} reqs={len(walls)} local_only={guard.local_only}",
    flush=True,
)
# barrier wait is skipped once the fleet is evicted — the dead peer
# will never file its marker
_exit_barrier("traffic", wait=not guard.local_only)
