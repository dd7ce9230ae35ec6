#!/usr/bin/env python
"""CI gate: request tracing accounts for every wall and the SLO plane
witnesses it — without taxing the untraced path.

Legs (ISSUE 19 acceptance):

1. **Attribution sums to wall** — a jittered storm through the async
   TrafficQueue with ``serve_trace_sample=1.0``: every answered future
   carries a finalized ledger whose stages sum to the request wall
   within 5%, the zero-steady-compile and p99-vs-p50 contracts hold
   WITH tracing armed, and ``serving_summary()`` gains attribution +
   slo blocks.
2. **Deterministic sampling** — the sampled-id set at
   ``serve_trace_sample=0.37`` is a pure hash of the trace id: a fresh
   subprocess recomputes the identical decisions (no RNG anywhere).
3. **Burn under breach** — a fake-clock SLOEngine fed an induced
   latency breach moves both burn-rate windows above 1.0, flips the
   multi-window breach flag, drains the error budget, and the live
   brownout/scale decisions RECORD the SLO state that witnessed them.
4. **oaptrace merges a 2-replica trace world** — a REAL 2-process
   fleet (leg-1 sharded sweep + traced storm, flight recorder + JSONL
   sinks armed) merges through dev/oaptrace.py into a validated
   recorder-mode timeline with request lanes AND ring-hop flow arrows
   spanning both replica tracks.  Hosts that cannot form a
   multiprocess jax world WARN and skip (the serve-gate convention).
5. **Disarmed seam** — with ``serve_trace_sample=0``, the tracing
   hooks (begin / note_flush / note_event / exemplar / finalize / SLO
   observe) price at <1% of the 20-predict serving microbench.

Exit 1 with the offending numbers on any violation.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

from serve_gate import (  # noqa: E402
    _spawn_traffic_world,
    _traffic_fields,
    check,
    failures,
)


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

    from oap_mllib_tpu import serving
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.serving import reqtrace
    from oap_mllib_tpu.serving import slo as slo_mod
    from oap_mllib_tpu.serving import traffic as traffic_mod
    from oap_mllib_tpu.telemetry import metrics as tm
    from oap_mllib_tpu.utils import progcache

    rng = np.random.default_rng(19)
    x = rng.normal(size=(1024, 16)).astype(np.float32)
    km = KMeans(k=6, seed=3, max_iter=4).fit(x[:500])
    hk = serving.serve(km)
    hk.warmup(1024)

    # -- leg 1: stages sum to wall on a jittered storm, contracts armed --
    print("== slo gate: attribution sums to wall on a traced jittered "
          "storm (sample=1.0) ==")
    set_config(serve_trace_sample=1.0, serve_slo_p99_ms=250.0)
    try:
        with serving.TrafficQueue(hk) as qw:
            for s in rng.integers(5, 512, size=12):  # warm wave
                qw.submit(x[: int(s)], deadline_ms=120_000).result(
                    timeout=60
                )
        compiles0 = progcache.xla_compile_count()
        with serving.TrafficQueue(hk) as q:
            subs = [
                (time.perf_counter(),
                 q.submit(x[: int(s)], deadline_ms=120_000))
                for s in rng.integers(5, 512, size=80)
            ]
            walls = []
            for ts, f in subs:
                f.result(timeout=120)
                walls.append(time.perf_counter() - ts)
        steady = progcache.xla_compile_count() - compiles0
        check(steady == 0,
              f"traced storm compiled {steady} programs (tracing must "
              "not perturb the zero-steady-compile contract)")
        walls.sort()
        p50, p99 = walls[len(walls) // 2], walls[-1]
        check(p99 <= max(50.0 * p50, 0.25),
              f"traced-storm p99 {p99 * 1e3:.1f} ms breaches the tail "
              f"bound (p50 {p50 * 1e3:.1f} ms)")
        ledgers = [reqtrace.ledger_of(f) for _, f in subs]
        missing = sum(
            1 for lg in ledgers if lg is None or lg.outcome != "answered"
        )
        check(missing == 0,
              f"{missing}/80 answered futures lack a finalized ledger")
        bad_cov = [
            (lg.ctx.trace_id, lg.stage_sum(), lg.wall_s)
            for lg in ledgers
            if lg is not None and lg.wall_s > 1e-6
            and abs(lg.stage_sum() - lg.wall_s) > 0.05 * lg.wall_s
        ]
        check(not bad_cov,
              f"{len(bad_cov)} ledgers miss the 5% sum-to-wall bound "
              f"(first: {bad_cov[:3]})")
        summ = serving.serving_summary()
        attr = summ.get("attribution", {})
        check(attr.get("traced", 0) >= 80,
              f"summary attribution traced={attr.get('traced')} < 80")
        check(0.95 <= attr.get("coverage", 0.0) <= 1.05,
              f"aggregate stage coverage {attr.get('coverage')} outside "
              "[0.95, 1.05]")
        check("slo" in summ and summ["slo"].get("armed") is True,
              "serving_summary() lacks an armed slo block")
        traced = int(tm.family_total("oap_serve_traced_total"))
        check(traced >= 92, f"oap_serve_traced_total {traced} < 92")
        print(f"  80-request storm: p50 {p50 * 1e3:.2f} ms, p99 "
              f"{p99 * 1e3:.2f} ms, coverage {attr.get('coverage')}, "
              f"0 compiles")
    finally:
        set_config(serve_trace_sample=0.0, serve_slo_p99_ms=0.0)
        slo_mod._reset_for_tests()

    # -- leg 2: sampling is a pure hash — identical across processes ----
    print("== slo gate: deterministic sampling across processes "
          "(sample=0.37, no RNG) ==")
    local = "".join(
        "1" if reqtrace.is_sampled(reqtrace.make_trace_id(r, s), 0.37)
        else "0"
        for r in (0, 1, 2) for s in range(400)
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = (
        "import sys; sys.path.insert(0, sys.argv[1]); "
        "from oap_mllib_tpu.serving.reqtrace import is_sampled, "
        "make_trace_id; "
        "print(''.join('1' if is_sampled(make_trace_id(r, s), 0.37) "
        "else '0' for r in (0, 1, 2) for s in range(400)))"
    )
    env = dict(os.environ)
    env.pop("PYTHONHASHSEED", None)  # the decision must not depend on it
    remote = subprocess.run(
        [sys.executable, "-c", prog, repo],
        capture_output=True, text=True, env=env, timeout=120,
    ).stdout.strip()
    check(local == remote,
          "a fresh process sampled a DIFFERENT id set (sampling must "
          "be a pure hash of the trace id)")
    frac = local.count("1") / len(local)
    check(0.25 <= frac <= 0.50,
          f"sample=0.37 selected fraction {frac:.3f} (hash badly "
          "skewed)")
    print(f"  1200 ids: {local.count('1')} sampled ({frac:.3f}), "
          "identical in a fresh process")

    # -- leg 3: induced breach moves the burn gauges; decisions record --
    print("== slo gate: multi-window burn under an induced breach, "
          "decisions record SLO state ==")
    clock = [0.0]
    eng = serving.SLOEngine(
        p99_ms=100.0, availability=0.99, window_s=600.0,
        clock=lambda: clock[0],
    )
    for _ in range(200):  # healthy baseline
        clock[0] += 0.1
        eng.observe(0.010, ok=True)
    check(eng.burn_rate(eng.fast_window_s) == 0.0,
          "healthy baseline burns error budget")
    check(eng.budget_remaining() == 1.0,
          "healthy baseline drained the error budget")
    for _ in range(50):  # the breach: every request blows the target
        clock[0] += 0.1
        eng.observe(0.500, ok=True)
    st = eng.state()
    check(st["burn_rate_fast"] > 1.0,
          f"fast burn {st['burn_rate_fast']} not > 1.0 under breach")
    check(st["burn_rate_slow"] > 1.0,
          f"slow burn {st['burn_rate_slow']} not > 1.0 under breach")
    check(st["breach"] is True, "multi-window breach flag never flipped")
    check(st["error_budget_remaining"] < 1.0,
          "error budget untouched by a 50-request breach")
    check(tm.family_total("oap_slo_burn_rate") > 1.0,
          "oap_slo_burn_rate gauges never moved under the breach")
    set_config(serve_slo_p99_ms=100.0, serve_slo_availability=0.99,
               serve_slo_window_s=600.0)
    try:
        for _ in range(20):
            slo_mod.observe_request(0.5, ok=False)
        bc = serving.BrownoutController("auto")
        for _ in range(12):
            bc.observe(200, 100)  # sustained 2x over-budget: steps fire
        check(bc.steps and all("slo" in s for s in bc.steps),
              "brownout steps do not record the witnessed SLO state")
        sc = serving.ScaleController(1)
        d = sc.observe(queue_depth=0)
        check("slo" in d and d["slo"].get("breach") is True,
              f"scale decision lacks breach-state SLO record: {d}")
        check(slo_mod.slo_state().get("armed") is True,
              "slo_state() not armed with serve_slo_p99_ms set")
    finally:
        set_config(serve_slo_p99_ms=0.0, serve_slo_availability=0.999,
                   serve_slo_window_s=3600.0, serve_brownout="auto")
        traffic_mod._reset_for_tests()
        slo_mod._reset_for_tests()
    print(f"  breach: fast burn {st['burn_rate_fast']}, slow burn "
          f"{st['burn_rate_slow']}, budget "
          f"{st['error_budget_remaining']}; decisions carry slo records")

    # -- leg 4: 2-replica trace world merges through oaptrace -----------
    print("== slo gate: 2-replica traced fleet -> oaptrace request "
          "lanes + ring-hop flow arrows ==")
    _trace_world_leg()

    # -- leg 5: disarmed seam prices at <1% of the microbench -----------
    print("== slo gate: tracing-off seam vs the 20-predict "
          "microbench ==")
    set_config(serve_trace_sample=0.0, serve_slo_p99_ms=0.0)
    xs = x[:256]
    hk.predict(xs)  # warm
    t0 = time.perf_counter()
    for _ in range(20):
        hk.predict(xs)
    predict_wall = time.perf_counter() - t0
    reps = 2000
    t0 = time.perf_counter()
    for _ in range(reps):
        # one of each disarmed hook per request — a large overestimate
        # (submit checks the knob once; the rest are misses)
        reqtrace.armed()
        reqtrace.begin(0.0, 0, 1, 0.0)
        reqtrace.note_flush("bucket_pad", 0.0)
        reqtrace.note_event("ring_hop", "", 0.0)
        reqtrace.exemplar_trace_id()
        reqtrace.finalize(None, "answered", 0.0)
        slo_mod.observe_request(0.0, True)
    seam_wall = (time.perf_counter() - t0) * (20.0 / reps)
    pct = 100.0 * seam_wall / predict_wall
    print(f"  20-predict wall {predict_wall * 1e3:.1f} ms; disarmed "
          f"hooks {seam_wall * 1e3:.3f} ms (~{pct:.2f}%)")
    check(seam_wall < max(0.01 * predict_wall, 0.005),
          f"disarmed tracing seam measurable: {seam_wall:.4f}s vs "
          f"{predict_wall:.4f}s predict wall")

    if failures:
        print(f"\nslo gate: {len(failures)} failure(s)")
        return 1
    print("\nslo gate: OK")
    return 0


def _trace_world_leg():
    with tempfile.TemporaryDirectory() as crash_dir:
        sink = os.path.join(crash_dir, "trace.jsonl")
        spawned = _spawn_traffic_world(
            "trace", 2, crash_dir, timeout=240,
            env_extra={"TRAFFIC_TRACE_SINK": sink},
        )
        if spawned is None:
            return
        procs, outs = spawned
        sweep_ok = True
        for r in range(2):
            check(procs[r].returncode == 0,
                  f"trace-world rank {r} failed:\n{outs[r][-1500:]}")
            fields = _traffic_fields(outs[r], f"TRACE_OK rank={r}")
            check(fields is not None,
                  f"rank {r} never finished the traced storm")
            if fields is not None:
                check(fields["missing"] == "0",
                      f"rank {r}: {fields['missing']} futures lack "
                      "finalized ledgers")
                check(fields["bad_cov"] == "0",
                      f"rank {r}: {fields['bad_cov']} ledgers miss the "
                      "5% sum-to-wall bound")
                check(int(fields["sampled"]) == int(fields["reqs"]),
                      f"rank {r}: sample=1.0 sampled "
                      f"{fields['sampled']}/{fields['reqs']}")
                # the worker degrades to a collective-free traced storm
                # on hosts whose backend cannot RUN sharded programs
                # (worlds form, computations don't) — ring-hop flows
                # are only expected where the sweep actually ran
                sweep_ok = sweep_ok and fields.get("sweep") == "1"
        import oaptrace

        paths = oaptrace.expand_paths([sink])
        check(len(paths) == 2, f"expected 2 per-rank sinks, got {paths}")
        trace = oaptrace.merge_trace(paths)
        problems = oaptrace.validate_trace(trace)
        check(problems == [],
              f"merged trace fails schema validation: {problems[:5]}")
        check(trace["otherData"]["mode"] == "recorder",
              "trace world merged without recorder events")
        check(trace["otherData"]["requests"] > 0,
              "no request-ledger records reached the sinks")
        lanes = [
            e for e in trace["traceEvents"]
            if e.get("cat") == "request" and e.get("ph") == "X"
        ]
        check({e["pid"] for e in lanes} == {0, 1},
              "request stage lanes missing from a replica track")
        ring = [
            e for e in trace["traceEvents"]
            if e.get("cat") == "ring_hop" and e.get("ph") in ("s", "t", "f")
        ]
        if sweep_ok:
            check(len(ring) >= 2, "no ring-hop flow arrows in the merge")
            check(len({e["pid"] for e in ring}) == 2,
                  "ring-hop flow arrows do not span both replica tracks")
            ring_note = (f"{len(ring)} ring-hop flow endpoints across "
                         "2 replica tracks")
        else:
            ring_note = ("ring hops skipped — this backend cannot run "
                         "sharded programs (tests/test_oaptrace.py "
                         "covers the flow chains synthetically)")
        print(f"  merged {trace['otherData']['requests']} request "
              f"ledgers, {len(lanes)} stage slices, {ring_note}")


if __name__ == "__main__":
    sys.exit(main())
