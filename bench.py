#!/usr/bin/env python
"""Benchmarks: K-Means / PCA / ALS on the accelerated path.

The default run needs the chip (an unknown ``device_kind`` raises — a
CPU has no peak to divide by) and prints one bound-annotated JSON line
per estimator, the headline being K-Means iters/sec at 1M x 256 f32,
k=1000:

  {"metric": ..., "value": N, "unit": "iters/sec", "vs_baseline": N, ...}

``python bench.py --all`` adds both K-Means precision tiers, the
largest-d single-chip PCA proxy with per-phase slope attribution, and
ALS at MovieLens-25M scale — the analog of the reference's per-phase
timing printouts (PCADALImpl.cpp:71-159, ALSDALImpl.cpp:429-436), but
recorded instead of scrolled away.  ``--mesh N`` runs the weak-scaling
harness.

K-Means/PCA lines report achieved TFLOP/s and MFU against the chip's bf16
peak.  Timings are best-of-N.  ``vs_baseline`` is the speedup over this
framework's own CPU/NumPy reference path (the vanilla-Spark-MLlib
analog; the reference repo publishes no numbers), measured live on a
subsample and scaled linearly to the full size.

One process holds the chip: this script starts no child that needs it
(``tests_tpu/`` is a command of its own), and the one leg that spawns
workers (``bench_serving_mp``) pins them to the CPU.  The compile cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at ``.jax_cache/``
beside this file.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# bf16 peak FLOP/s by device kind (the MFU denominator)
_PEAK = {
    "TPU v6": 918e12,
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
}


def _peak_flops():
    """bf16 peak of the device this process holds.  A device that is not
    in the table is an error, not a default: an MFU against a guessed
    peak is a number about nothing — and a CPU, which has no entry,
    thereby fails every measurement path that reports one."""
    import jax

    kind = jax.devices()[0].device_kind
    for key, val in _PEAK.items():
        if kind.startswith(key):
            return val
    raise RuntimeError(
        f"bench: no bf16 peak on record for device_kind {kind!r} "
        f"(platform {jax.devices()[0].platform!r}); known: "
        f"{sorted(_PEAK)} — the benchmark measures a chip, add the "
        "device's published peak with its source or run on one"
    )


def _best_of(fn, reps=3, warm=True):
    """Best wall time over reps."""
    if warm:
        fn()  # warm-up/compile of the exact timed variant
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# single-chip ALS gather ceiling taken on a v5e before PR 1: XLA's TPU
# gather moved padded edge indices at ~250M indices/s regardless of
# layout (the bound is per-index, not per-byte); not re-measured since
_ALS_GATHER_CEILING = 250e6


def _bound_extras(kind, achieved, bound):
    """Uniform achieved-vs-bound annotation: every
    per-algorithm headline line names its achieved rate, the bound it is
    measured against, and the fraction — so a round-over-round regression
    in ANY algorithm surfaces in the driver-captured JSON."""
    return {
        "bound_kind": kind,
        "achieved": round(achieved, 3),
        "bound": round(bound, 3),
        "bound_frac": round(achieved / bound, 4) if bound else None,
    }


def _sanitizers_state() -> str:
    """The armed sanitizer set as a stable string ("off" when empty) —
    recorded in every bench JSON line so runs are comparable: the
    collective sanitizer adds a cross-check gather per host collective
    and the retrace guard changes compile behavior, so numbers from
    runs with different sanitizer sets must never be diffed silently."""
    from oap_mllib_tpu.utils import sanitizers

    names = sorted(sanitizers.enabled_set())
    return ",".join(names) if names else "off"


def _emit(metric, value, unit, vs_baseline, **extra):
    import jax

    line = {
        "metric": metric,
        "value": round(value, 4),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 2),
        "sanitizers": _sanitizers_state(),
        # every line names its backend so trajectory tooling
        # (dev/bench_regress.py) never diffs numbers across backends
        "backend": jax.default_backend(),
    }
    if "locks" in _sanitizers_state():
        # the locks sanitizer's hold-time tail rides the line so a
        # locks-armed capture explains its own latency inflation
        from oap_mllib_tpu.utils import locktrace

        line["lock_hold_p99_ms"] = round(
            locktrace.hold_quantile(0.99) * 1e3, 4)
    if "kernel" in extra:
        # every kernel-bearing line names the autotune policy it ran
        # under — numbers from a swept/pinned run must never be diffed
        # silently against hand-picked-default numbers
        from oap_mllib_tpu.config import get_config

        line["tuning"] = get_config().tuning.split(":", 1)[0]
    line.update(extra)
    print(json.dumps(line), flush=True)


# The one compile cache of a bench process when JAX_COMPILATION_CACHE_DIR
# is unset (progcache.use_checkout_cache): beside this file, git-ignored,
# and fixed — the path is part of the cache key, so a directory that
# moves never hits.
_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".jax_cache"
)


def _compile_extras(timings, phase, cache_delta=None):
    """Compile-amortization report for a fit (rides next to the overlap
    metrics): the ``<phase>/compile`` vs ``/execute`` wall split the
    program-cache launch wrappers record (utils/progcache.launch —
    compile = first-seen-program launches, execute = cache-hit
    launches), plus the fit's registry hit rate."""
    out = {}
    split = timings.compile_split(phase) if timings is not None else None
    if split is not None:
        out["compile_sec"] = round(split["compile"], 3)
        out["execute_sec"] = round(split["execute"], 3)
    if cache_delta:
        out["progcache_hits"] = cache_delta["hits"]
        out["progcache_misses"] = cache_delta["misses"]
        if cache_delta.get("hit_rate") is not None:
            out["progcache_hit_rate"] = round(cache_delta["hit_rate"], 3)
    return out


# ---------------------------------------------------------------------------
# K-Means (headline)
# ---------------------------------------------------------------------------


def bench_kmeans(precision="highest", cpu_ips=None, extra=None,
                 policy="f32"):
    import jax
    import jax.numpy as jnp

    from oap_mllib_tpu.ops import kmeans_ops

    n, d, k = 1 << 20, 256, 1000
    # 100 iterations per timed run, so the per-call dispatch + fetch is
    # a small share of the window (real fits at this scale run the loop
    # for hundreds of iterations).  The executed n_iter is divided by,
    # so early exact convergence cannot inflate the number (the
    # round-1/2 bug).
    iters = 100
    rng = np.random.default_rng(0)
    # blob-ish data so assignments are non-degenerate
    proto = rng.normal(size=(k, d)).astype(np.float32)
    x = proto[rng.integers(k, size=n)] + rng.normal(size=(n, d)).astype(np.float32) * 0.3
    w = np.ones((n,), np.float32)
    # RANDOM-ROW init, not proto+epsilon: a near-optimal init converges in
    # ~2 Lloyd iterations and tol=0 does NOT prevent the stop (exactly-zero
    # moves satisfy <= 0), so rounds 1-2 timed 2 iterations while dividing
    # by 10 — every prior recorded kmeans bench number was inflated.  The
    # actual executed n_iter is now fetched, divided by, and recorded.
    init = x[rng.choice(n, size=k, replace=False)]

    xj = jax.device_put(jnp.asarray(x))
    wj = jnp.asarray(w)
    cj = jnp.asarray(init)
    tol = jnp.asarray(0.0, jnp.float32)
    # the estimator's own route — one shared function, cannot diverge
    from oap_mllib_tpu.config import get_config
    from oap_mllib_tpu.parallel.mesh import get_mesh

    route = kmeans_ops.lloyd_route(
        get_config(), get_mesh(n_devices=1), n, d, k, np.float32, precision
    )
    use_pallas = route.kernel == "pallas"

    def run():
        c, it, cost, _ = kmeans_ops.lloyd_run(
            xj, wj, cj, iters, tol, route.row_chunks, precision,
            policy=policy, accumulate=route.kernel, **route.geometry,
        )
        # fetching the centers synchronizes
        return np.asarray(c), int(it)

    from oap_mllib_tpu.utils import progcache

    xla_before = progcache.xla_compile_count()
    t0 = time.perf_counter()
    n_iter = run()[1]  # warm-up/compile; n_iter is deterministic
    t_first = time.perf_counter() - t0  # first call = trace+compile+run
    # 5 reps: this is THE recorded headline — extra reps are cheap
    # insurance against host jitter
    reps = 5
    dt = _best_of(lambda: run()[0], reps=reps, warm=False)
    iters_per_sec = n_iter / dt
    # compile-amortized throughput: every iteration this process ran,
    # divided by every second it spent (first-call compile included) —
    # what a one-shot caller actually gets vs the steady-state headline
    amortized_ips = n_iter * (reps + 1) / (t_first + reps * dt)
    flops = 2 * 2 * n * k * d  # two n*k*d matmuls per iteration
    tflops = flops * iters_per_sec / 1e12

    if cpu_ips is None:
        # CPU reference baseline: one Lloyd pass on a subsample, scaled to n
        sub = 1 << 14
        from oap_mllib_tpu.fallback.kmeans_np import lloyd_np

        t0 = time.perf_counter()
        lloyd_np(x[:sub].astype(np.float64), init.astype(np.float64), 1, 0.0, w[:sub])
        t_cpu_sub = time.perf_counter() - t0
        cpu_ips = 1.0 / (t_cpu_sub * (n / sub))

    suffix = "" if precision == "high" else f"_{precision}"
    size = f"{n >> 20}M" if n >= (1 << 20) else f"{n >> 10}K"
    metric = f"kmeans_{size}x{d}_k{k}_iters_per_sec"
    # the recorded precision follows the COMPUTE POLICY (no longer
    # hardwired to a tier): an f32 policy keeps the legacy tier string,
    # a reduced policy names itself
    _emit(
        f"{metric}{suffix}",
        iters_per_sec,
        "iters/sec",
        iters_per_sec / cpu_ips,
        tflops=round(tflops, 1),
        mfu=round(tflops * 1e12 / _peak_flops(), 3),
        **_bound_extras("bf16_peak_tflops", tflops, _peak_flops() / 1e12),
        precision=precision if policy == "f32" else policy,
        compute_precision=policy,
        matmul_tier=precision,
        n_iter=n_iter,
        kernel="pallas" if use_pallas else "xla",
        compile_sec=round(max(t_first - dt, 0.0), 2),
        amortized_iters_per_sec=round(amortized_ips, 3),
        xla_compiles=progcache.xla_compile_count() - xla_before,
        **(extra or {}),
    )
    return iters_per_sec, cpu_ips


# ---------------------------------------------------------------------------
# PCA
# ---------------------------------------------------------------------------


def _slope(run_with_reps, r1=1, target_delta=0.8, r2_cap=2048, reps=3):
    """Per-op seconds via an in-jit repeat slope: (t(r2) - t(r1)) /
    (r2 - r1) cancels the constant per-call dispatch+fetch that a
    single-call wall would book against the kernel.

    Two constraints: the repeat count must be a RUNTIME loop bound
    (lax.fori_loop), not a static scan length — eigh at d=2048 compiles
    for minutes, so both window sizes must share one executable — and
    the window must be WORK-CALIBRATED (a quick probe sizes r2 so the
    delta is ~``target_delta`` seconds): fixed small windows put
    ms-scale per-op deltas under the host's timing jitter and read as
    zero."""
    run_with_reps(r1)  # one compile (dynamic trip count) + warm
    t_r1 = _best_of(lambda: run_with_reps(r1), reps=2, warm=False)
    probe_r = min(r2_cap, 4 * r1 + 8)
    t_probe = _best_of(lambda: run_with_reps(probe_r), reps=2, warm=False)
    per = max((t_probe - t_r1) / (probe_r - r1), 1e-5)
    r2 = min(r2_cap, r1 + max(8, int(target_delta / per)))
    # the probe's r1 samples count toward the final best-of (no reason to
    # pay the ~0.1-0.4 s dispatch for duplicate r1 windows)
    t1 = min(t_r1, _best_of(lambda: run_with_reps(r1), reps=1, warm=False))
    t2 = _best_of(lambda: run_with_reps(r2), reps=reps, warm=False)
    return max(t2 - t1, 1e-9) / (r2 - r1)


def bench_pca(n=1 << 20, d=128):
    """PCA with per-phase kernel attribution: the
    covariance Gram and the eigh are slope-measured SEPARATELY inside
    jitted repeat loops, so the recorded numbers are kernel times (the
    33-GFLOP Gram at 1M x 128 is sub-ms of MXU time, so a single-call
    wall is mostly dispatch).  The end-to-end wall (one call incl.
    dispatch + fetch) is still the headline value for continuity."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from oap_mllib_tpu.config import get_config
    from oap_mllib_tpu.ops import pca_ops

    rng = np.random.default_rng(1)
    x = rng.normal(size=(n, d)).astype(np.float32)
    xj = jax.device_put(jnp.asarray(x))
    mask = jnp.ones((n,), jnp.float32)
    n_rows = jnp.asarray(float(n), jnp.float32)

    def run():
        cov, _ = pca_ops.covariance(xj, mask, n_rows)
        vals, _ = pca_ops.eigh_descending(cov)
        return np.asarray(vals)  # host fetch = sync

    dt = _best_of(run)

    # phase 1: covariance (two-pass centered Gram at HIGHEST).  The
    # carry-perturbed mask (numerically nil) defeats loop-invariant code
    # motion hoisting the otherwise-identical Gram out of the loop.
    @functools.partial(jax.jit)
    def cov_reps(xr, m, nr, reps):
        def body(i, acc):
            cov, _ = pca_ops.covariance(xr, m + acc[0, 0] * 1e-30, nr)
            return acc + cov

        return lax.fori_loop(
            0, reps, body, jnp.zeros((d, d), xr.dtype)
        )

    cov_sec = _slope(lambda r: np.asarray(cov_reps(xj, mask, n_rows, r)))

    # phase 2: eigh (the finalizeCompute analog), same protocol
    cov0 = jax.device_put(pca_ops.covariance(xj, mask, n_rows)[0])

    @functools.partial(jax.jit)
    def eigh_reps(cov, reps):
        def body(i, acc):
            _, vecs = pca_ops.eigh_descending(cov + acc * 1e-30)
            return acc + vecs

        return lax.fori_loop(0, reps, body, jnp.zeros_like(cov))

    eigh_sec = _slope(lambda r: np.asarray(eigh_reps(cov0, r)))

    cov_flops = 2 * n * d * d  # centered Gram matmul (mean pass is O(nd))
    cov_tflops = cov_flops / cov_sec / 1e12

    # NumPy f64 baseline: covariance on a subsample scaled linearly in n
    # (Gram is linear in n); eigh timed once at full size (it is O(d^3),
    # independent of n — scaling it would overstate the baseline)
    sub = min(n, 1 << 16)
    t0 = time.perf_counter()
    xs = x[:sub].astype(np.float64)
    mu = xs.mean(axis=0)
    cov_np = (xs.T @ xs - sub * np.outer(mu, mu)) / (sub - 1)
    t_cov = (time.perf_counter() - t0) * (n / sub)
    t0 = time.perf_counter()
    np.linalg.eigh(cov_np)
    t_cpu = t_cov + (time.perf_counter() - t0)

    size = f"{n >> 20}M" if n >= (1 << 20) else f"{n >> 10}k"
    _emit(
        f"pca_{size}x{d}_cov_eigh_sec",
        dt,
        "sec",
        t_cpu / dt,
        cov_sec=round(cov_sec, 5),
        eigh_sec=round(eigh_sec, 5),
        dispatch_sec=round(max(dt - cov_sec - eigh_sec, 0.0), 4),
        cov_tflops=round(cov_tflops, 1),
        cov_mfu=round(cov_tflops * 1e12 / _peak_flops(), 3),
        # which Gram kernel the dispatch rule picked for this shape —
        # the ISSUE 9 fused Pallas moments kernel on TPU, XLA elsewhere
        kernel=(
            "pallas"
            if pca_ops.use_pallas_gram(
                get_config().pca_kernel, d, "highest", np.float32
            )
            else "xla"
        ),
        # eigh's share of the end-to-end wall: a growing share at fixed
        # d means the O(d^3) finalize (not the Gram) regressed
        eigh_wall_share=round(eigh_sec / dt, 4),
        **_bound_extras("bf16_peak_tflops", cov_tflops,
                        _peak_flops() / 1e12),
    )
    return dt


# ---------------------------------------------------------------------------
# ALS
# ---------------------------------------------------------------------------


def _als_solve_extras(n_users, n_items, rank, sec_per_iter):
    """MFU-style annotation for the ALS normal-equation SOLVE kernel
    (ISSUE 9): analytic solve+assembly FLOPs per iteration — both
    halves Cholesky-factor (2/3·r³) and doubly-substitute (4·r²) one
    system per user/item row — over the iteration wall, next to the
    gather bound.  A lower bound on solve intensity (the wall includes
    the moment build), but a regression in the fused Pallas solve
    surfaces as a falling solve_mfu at fixed shape."""
    from oap_mllib_tpu.ops.als_ops import resolve_solve_kernel

    flops = (n_users + n_items) * (
        (2.0 / 3.0) * rank ** 3 + 4.0 * rank ** 2
    )
    solve_tflops = flops / sec_per_iter / 1e12
    return {
        "solve_tflops": round(solve_tflops, 4),
        "solve_mfu": round(solve_tflops * 1e12 / _peak_flops(), 6),
        "solve_kernel": resolve_solve_kernel(rank, np.float32),
    }


def bench_als():
    """MovieLens-1M scale: 6040 users x 3706 items, 1M ratings, rank 10,
    implicit, alpha=40 (the reference examples' DAL-path config,
    examples/als-pyspark/als-pyspark.py:52-54)."""
    import jax
    import jax.numpy as jnp

    from oap_mllib_tpu.fallback import als_np
    from oap_mllib_tpu.ops import als_ops

    n_users, n_items, nnz, rank = 6040, 3706, 1_000_000, 10
    # 25-iteration window: ALS runs its whole loop in ONE jitted call (no
    # early exit — lax.scan over max_iter), so like the K-Means bench the
    # window must be long enough that the per-call dispatch latency
    # doesn't dominate the per-iteration figure
    iters = 25
    rng = np.random.default_rng(2)
    users = rng.integers(n_users, size=nnz).astype(np.int32)
    items = rng.integers(n_items, size=nnz).astype(np.int32)
    ratings = (rng.random(nnz) * 4 + 1).astype(np.float32)
    x0 = als_np.init_factors(n_users, rank, 0)
    y0 = als_np.init_factors(n_items, rank, 1)

    # grouped-edge layout — the estimator's actual single-device hot path
    by_user = als_ops.build_grouped_edges(users, items, ratings, n_users)
    by_item = als_ops.build_grouped_edges(items, users, ratings, n_items)
    dev = tuple(jax.device_put(jnp.asarray(a)) for a in (*by_user, *by_item))
    x0j, y0j = jnp.asarray(x0), jnp.asarray(y0)

    def run():
        x, y = als_ops.als_run_grouped(
            *dev, x0j, y0j, n_users, n_items, iters, 0.1, 40.0, True
        )
        return np.asarray(x)

    dt = _best_of(run)
    sec_per_iter = dt / iters

    # NumPy fallback: one full-size iteration (no subsample scaling — the
    # per-user/item solve cost is independent of nnz, so scaling a
    # subsample time would overstate the baseline)
    t0 = time.perf_counter()
    als_np.als_np(
        users, items, ratings, n_users, n_items, rank,
        max_iter=1, reg=0.1, alpha=40.0, implicit=True, seed=0, init=(x0, y0),
    )
    t_cpu_iter = time.perf_counter() - t0

    # per iteration both halves gather their PADDED edge lists' source
    # factors once — the single-chip bottleneck when last attributed
    # on a v5e ("the grouped iteration is gather-bound")
    gathered = by_user[0].size + by_item[0].size
    _emit(
        "als_ml1m_implicit_sec_per_iter",
        sec_per_iter,
        "sec/iter",
        t_cpu_iter / sec_per_iter,
        **_bound_extras("gather_indices_per_sec",
                        gathered / sec_per_iter, _ALS_GATHER_CEILING),
        **_als_solve_extras(n_users, n_items, rank, sec_per_iter),
    )
    return sec_per_iter


def bench_als_large():
    """MovieLens-25M scale: 162,541 users x 59,047 items, 25M ratings,
    rank 10, implicit — the single-chip scale proof (the G-blocked
    grouped partials keep live intermediates ~256 MB; unchunked, lane
    padding alone needed 21 GB and OOM'd).  Item popularity is zipf(1.3)
    so the padding guard sees a real long tail."""
    import jax
    import jax.numpy as jnp

    from oap_mllib_tpu.fallback import als_np
    from oap_mllib_tpu.ops import als_ops

    n_users, n_items, nnz, rank = 162_541, 59_047, 25_000_000, 10
    iters = 10  # ~2.7 s per call: dispatch latency is already <5% here
    rng = np.random.default_rng(3)
    users = rng.integers(n_users, size=nnz).astype(np.int32)
    items = (np.random.default_rng(4).zipf(1.3, size=nnz) % n_items).astype(
        np.int32
    )
    ratings = (rng.random(nnz) * 4 + 1).astype(np.float32)
    x0 = als_np.init_factors(n_users, rank, 0)
    y0 = als_np.init_factors(n_items, rank, 1)

    by_user = als_ops.build_grouped_edges(users, items, ratings, n_users)
    by_item = als_ops.build_grouped_edges(items, users, ratings, n_items)
    dev = tuple(jax.device_put(jnp.asarray(a)) for a in (*by_user, *by_item))
    x0j, y0j = jnp.asarray(x0), jnp.asarray(y0)

    def run():
        x, y = als_ops.als_run_grouped(
            *dev, x0j, y0j, n_users, n_items, iters, 0.1, 40.0, True
        )
        return np.asarray(x)

    dt = _best_of(run)
    sec_per_iter = dt / iters

    # CPU reference: one iteration on a 1/25 subsample with the full
    # user/item universe — per-row solve cost dominates (162k + 59k
    # solves happen regardless of nnz), so this UNDERSTATES the full-size
    # CPU time; the recorded speedup is therefore a floor
    sub = nnz // 25
    t0 = time.perf_counter()
    als_np.als_np(
        users[:sub], items[:sub], ratings[:sub], n_users, n_items, rank,
        max_iter=1, reg=0.1, alpha=40.0, implicit=True, seed=0, init=(x0, y0),
    )
    t_cpu_iter = time.perf_counter() - t0

    gathered = by_user[0].size + by_item[0].size
    _emit(
        "als_ml25m_implicit_sec_per_iter",
        sec_per_iter,
        "sec/iter",
        t_cpu_iter / sec_per_iter,
        **_bound_extras("gather_indices_per_sec",
                        gathered / sec_per_iter, _ALS_GATHER_CEILING),
        **_als_solve_extras(n_users, n_items, rank, sec_per_iter),
    )
    return sec_per_iter


# ---------------------------------------------------------------------------
# Multi-chip weak-scaling harness (bench.py --mesh N)
# ---------------------------------------------------------------------------


def _mesh_of(m):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:m]).reshape(m), ("data",))


def bench_mesh(n_devices: int, backend: str = "cpu", sizes: str = "small"):
    """Weak-scaling protocol over 1..n_devices ranks: per-rank work is
    FIXED and the global problem grows with the mesh, for all three
    estimator kernels.  One JSON line per (kernel, mesh) with wall time,
    per-rank work, and the analytic per-iteration collective payload
    (allreduce counted 2x payload x (m-1)/m).

    The same entry point runs unchanged on a real slice
    (``--mesh-backend real``); with ``backend="cpu"`` (the default, and
    what CI pins at N=8) the ranks are VIRTUAL CPU devices sharing one
    host — wall times then measure protocol/compute overheads, NOT ICI
    scaling, and every line carries ``"virtual_cpu": true`` to say so.
    ``sizes="big"`` selects slice-scale shapes for real hardware."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"--mesh {n_devices} needs {n_devices} devices, backend has "
            f"{len(jax.devices())} (forcing the virtual CPU mesh failed — "
            "a backend initialized before bench_mesh could configure it?)"
        )
    virtual = jax.default_backend() == "cpu" and backend == "cpu"
    big = sizes == "big"
    rng = np.random.default_rng(7)

    meshes = [1]
    while meshes[-1] * 2 <= n_devices:
        meshes.append(meshes[-1] * 2)
    if meshes[-1] != n_devices:  # --mesh 6: [1, 2, 4, 6], never skip N
        meshes.append(n_devices)

    # -- K-Means: per-rank rows fixed -------------------------------------
    from oap_mllib_tpu.ops import kmeans_ops

    rows_per_rank, d, k = (1 << 18, 256, 256) if big else (1 << 14, 32, 16)
    iters = 10
    for m in meshes:
        n = rows_per_rank * m
        x = rng.normal(size=(n, d)).astype(np.float32)
        init = x[rng.choice(n, size=k, replace=False)]
        mesh = _mesh_of(m)
        xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data", None)))
        ws = jax.device_put(
            jnp.ones((n,), jnp.float32), NamedSharding(mesh, P("data"))
        )
        cj = jnp.asarray(init)
        tol = jnp.asarray(0.0, jnp.float32)
        chunks = kmeans_ops.auto_row_chunks(rows_per_rank, k)

        def run():
            c, it, _, _ = kmeans_ops.lloyd_run(
                xs, ws, cj, iters, tol, chunks, "highest"
            )
            return np.asarray(c), int(it)

        n_iter = run()[1]
        dt = _best_of(lambda: run()[0], reps=2, warm=False)
        _emit(
            "mesh_scaling_kmeans", dt / max(n_iter, 1), "sec/iter", 1.0,
            mesh=m, per_rank_rows=rows_per_rank, d=d, k=k,
            collective_bytes_per_iter=int(
                2 * (k * d + k) * 4 * (m - 1) / max(m, 1)
            ),
            virtual_cpu=virtual,
        )

    # -- PCA: per-rank rows fixed -----------------------------------------
    from oap_mllib_tpu.ops import pca_ops

    rows_per_rank, d = (1 << 18, 512) if big else (1 << 15, 128)
    for m in meshes:
        n = rows_per_rank * m
        x = rng.normal(size=(n, d)).astype(np.float32)
        mesh = _mesh_of(m)
        xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data", None)))
        ws = jax.device_put(
            jnp.ones((n,), jnp.float32), NamedSharding(mesh, P("data"))
        )
        nr = jnp.asarray(float(n), jnp.float32)

        def run():
            cov, _ = pca_ops.covariance(xs, ws, nr)
            return np.asarray(cov)

        dt = _best_of(run, reps=2)
        _emit(
            "mesh_scaling_pca_cov", dt, "sec", 1.0,
            mesh=m, per_rank_rows=rows_per_rank, d=d,
            collective_bytes_per_iter=int(
                2 * (d * d + d) * 4 * (m - 1) / max(m, 1)
            ),
            virtual_cpu=virtual,
        )

    # -- ALS: per-rank edges + user rows fixed, replicated item layout ----
    from oap_mllib_tpu.ops import als_block

    edges_per_rank, users_per_rank, n_items, r = (
        (1 << 21, 1 << 18, 1 << 16, 10) if big else (100_000, 10_000, 5_000, 8)
    )
    als_iters = 3
    for m in meshes:
        nnz = edges_per_rank * m
        n_users = users_per_rank * m
        u = rng.integers(0, n_users, nnz).astype(np.int64)
        i = rng.integers(0, n_items, nnz).astype(np.int64)
        rr = (rng.random(nnz) * 4 + 1).astype(np.float32)
        mesh = _mesh_of(m)
        u_loc, i_glob, conf, valid, offsets, upb = (
            als_block.prepare_block_inputs(u, i, rr, mesh, n_users)
        )
        grouped = als_block.prepare_grouped_inputs(
            u_loc, i_glob, conf, valid, mesh, upb, n_items
        )
        from jax.sharding import NamedSharding as NS

        x0 = jax.device_put(
            (rng.normal(size=(mesh.shape["data"] * upb, r)) * 0.1).astype(
                np.float32
            ),
            NS(mesh, P("data", None)),
        )
        y0 = jax.device_put(
            (rng.normal(size=(n_items, r)) * 0.1).astype(np.float32),
            NS(mesh, P()),
        )

        def run():
            bx, by = als_block.als_block_run_grouped(
                grouped, x0, y0, als_iters, 0.1, 1.0, mesh, implicit=True
            )
            return np.asarray(by)

        dt = _best_of(run, reps=2)
        _emit(
            "mesh_scaling_als", dt / als_iters, "sec/iter", 1.0,
            mesh=m, per_rank_edges=edges_per_rank,
            per_rank_users=users_per_rank, n_items=n_items, rank=r,
            item_layout="replicated",
            collective_bytes_per_iter=int(
                2 * (n_items * r * (r + 1) + r * r) * 4 * (m - 1) / max(m, 1)
            ),
            virtual_cpu=virtual,
        )

        # the 2-D item-sharded layout on the same edges/sizes: second
        # shuffle by item block, Y block-sharded, all_gather exchanges
        i_loc, u_glob, conf_i, valid_i, _, ipb = (
            als_block.prepare_block_inputs(i, u, rr, mesh, n_items)
        )
        grouped2 = als_block.prepare_grouped_inputs_2d(
            u_loc, i_glob, conf, valid, i_loc, u_glob, conf_i, valid_i,
            mesh, upb, ipb,
        )
        y0_sh = jax.device_put(
            (rng.normal(size=(m * ipb, r)) * 0.1).astype(np.float32),
            NS(mesh, P("data", None)),
        )

        def run_sh():
            bx, by = als_block.als_block_run_grouped_2d(
                grouped2, x0, y0_sh, als_iters, 0.1, 1.0, mesh,
                implicit=True,
            )
            return np.asarray(by)

        dt = _best_of(run_sh, reps=2)
        _emit(
            "mesh_scaling_als", dt / als_iters, "sec/iter", 1.0,
            mesh=m, per_rank_edges=edges_per_rank,
            per_rank_users=users_per_rank, n_items=n_items, rank=r,
            item_layout="sharded",
            # two tiled all_gathers (X, Y) + TWO r*r Gram allreduces
            # (allreduce = 2x payload, the same convention as every
            # other formula in this file)
            collective_bytes_per_iter=int(
                ((n_users + n_items) * r + 4 * r * r)
                * 4 * (m - 1) / max(m, 1)
            ),
            virtual_cpu=virtual,
        )

        # the streamed out-of-core composition on the same edges: each
        # rank's grouped layouts stay HOST-resident and stream through
        # its device in chunks (ops/als_block_stream); the collective
        # structure matches the replicated run above, so the delta vs
        # mesh_scaling_als is the upload-per-iteration price
        from oap_mllib_tpu.ops import als_block_stream

        lay = als_block_stream.prepare_streamed_block_layouts(
            u, i, rr, n_users, n_items, mesh, r, item_sharded=False
        )

        def run_st():
            bx, by = als_block_stream.als_block_run_streamed(
                lay, x0, y0, als_iters, 0.1, 1.0, mesh, implicit=True
            )
            return np.asarray(by)

        dt = _best_of(run_st, reps=2)
        # one instrumented run for the prefetch split: how much of the
        # per-iteration upload price the pipeline hides behind the
        # moment kernels (the delta vs mesh_scaling_als is the price;
        # overlap_efficiency is the hidden fraction)
        from oap_mllib_tpu.utils.timing import Timings

        t_st = Timings()
        als_block_stream.als_block_run_streamed(
            lay, x0, y0, als_iters, 0.1, 1.0, mesh, implicit=True,
            timings=t_st,
        )
        eff = t_st.overlap_efficiency("als_iterations")
        sub = t_st.subphases("als_iterations")
        _emit(
            "mesh_scaling_als_streamed", dt / als_iters, "sec/iter", 1.0,
            mesh=m, per_rank_edges=edges_per_rank,
            per_rank_users=users_per_rank, n_items=n_items, rank=r,
            item_layout="replicated", virtual_cpu=virtual,
            overlap_efficiency=None if eff is None else round(eff, 3),
            transfer_sec=round(sub.get("transfer", 0.0), 3),
        )


# ---------------------------------------------------------------------------
# North-star streamed scale (bench.py --streamed ROWS)
# ---------------------------------------------------------------------------


def bench_streamed(rows: int, d: int = 256, k: int = 1000,
                   max_iter: int = 2):
    """Streamed K-Means + PCA at north-star row counts (BASELINE.json's
    100M x 256 config): a generator-backed ChunkSource synthesizes the
    table on the fly — host RAM holds one ~1 GB base buffer and one
    chunk, device HBM one chunk + the running state — so THE SAME
    command scales to any row count the wall clock affords:

        python bench.py --streamed 100000000     # full north star (pod host)
        python bench.py --streamed 10000000      # a one-chip point

    Emits the measured host->device bandwidth first: where it, not
    compute, bounds the per-pass time, the JSON records both so a
    reader can tell.
    """
    import jax

    from oap_mllib_tpu.data.stream import ChunkSource
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.models.pca import PCA

    if rows < k:
        raise SystemExit(
            f"--streamed ROWS must be >= k={k} (got {rows}); the point of "
            "this mode is north-star row counts"
        )
    chunk_rows = 1 << 16
    base_n = min(rows, 1 << 20)
    rng = np.random.default_rng(0)
    proto = rng.normal(size=(k, d)).astype(np.float32) * 4
    x_base = (
        proto[rng.integers(k, size=base_n)]
        + rng.normal(size=(base_n, d)).astype(np.float32) * 0.3
    )

    def gen():
        remaining = rows
        while remaining > 0:
            take = min(base_n, remaining)
            yield x_base[:take]
            remaining -= take

    # raw ingest bandwidth at the fit's own chunk size — the bound this
    # environment puts on every per-pass number below
    probe = x_base[:chunk_rows]
    _ = np.asarray(jax.device_put(probe)[0, 0])  # warm (sync via fetch)
    t_up = _best_of(
        lambda: np.asarray(jax.device_put(probe)[0, 0]), reps=3, warm=False
    )
    mbps = probe.nbytes / t_up / 1e6
    _emit("host_to_device_MBps", mbps, "MB/s", 1.0,
          chunk_mb=probe.nbytes >> 20)

    # CPU per-pass reference (one Lloyd pass on a subsample, scaled)
    sub = min(1 << 14, base_n)
    from oap_mllib_tpu.fallback.kmeans_np import lloyd_np

    t0 = time.perf_counter()
    lloyd_np(
        x_base[:sub].astype(np.float64),
        x_base[rng.choice(base_n, size=k, replace=False)].astype(np.float64),
        1, 0.0, np.ones((sub,), np.float64),
    )
    cpu_pass = (time.perf_counter() - t0) * (rows / sub)

    def _resilience_extras(summary):
        """Fault accounting for a long streamed run (utils/resilience
        .py): at north-star scale a pass takes minutes, so retries and
        degradations that silently stretched the wall must be visible in
        the metric they stretched."""
        res = (
            summary.get("resilience") if isinstance(summary, dict)
            else getattr(summary, "resilience", None)
        )
        if not res or not res.get("faults"):
            return {}
        return {
            "fault_retries": res["retries"],
            "fault_degradations": res["degradations"],
            "fault_backoff_sec": round(res["backoff_s"], 3),
        }

    def _checkpoint_extras(summary):
        """Checkpoint write overhead for a streamed run (ROADMAP item 4
        follow-on): when elastic-worlds checkpointing is armed, report
        the per-interval insurance premium — bytes and seconds per
        checkpoint interval — next to the per-pass numbers it taxes."""
        ck = (
            summary.get("checkpoint") if isinstance(summary, dict)
            else getattr(summary, "checkpoint", None)
        )
        if not ck or not ck.get("writes"):
            return {}
        return {
            "ckpt_writes": ck["writes"],
            "ckpt_bytes_per_interval": round(
                ck["bytes_written"] / ck["writes"]),
            "ckpt_sec_per_interval": round(
                ck["write_seconds"] / ck["writes"], 4),
        }

    def _overlap_extras(timings, phase):
        """Prefetch-pipeline report for a streamed phase: the
        stage/transfer/compute split (data/prefetch.py) and the fraction
        of staging hidden behind compute.  The split proves WHERE a
        streamed pass spends its wall — a transfer-bound environment shows
        transfer ~= compute with high overlap; a compute-bound one shows
        staging fully hidden."""
        eff = timings.overlap_efficiency(phase)
        if eff is None:
            return {}
        sub = timings.subphases(phase)
        return {
            "overlap_efficiency": round(eff, 3),
            "stage_sec": round(sub.get("stage", 0.0), 3),
            "transfer_sec": round(sub.get("transfer", 0.0), 3),
            "compute_sec": round(sub.get("compute", 0.0), 3),
        }

    src = ChunkSource(gen, d, chunk_rows=chunk_rows, n_rows=rows)
    t0 = time.perf_counter()
    m = KMeans(k=k, seed=1, init_mode="random", max_iter=max_iter).fit(src)
    t_fit = time.perf_counter() - t0
    assert getattr(m.summary, "streamed", False)
    ph = m.summary.timings.as_dict()
    n_iter = max(int(m.summary.num_iter), 1)
    per_pass = ph["lloyd_loop"] / n_iter
    bytes_per_pass = rows * d * 4
    _emit(
        f"streamed_kmeans_{rows}x{d}_k{k}_sec_per_pass",
        per_pass, "sec/pass", cpu_pass / per_pass,
        rows_per_sec=round(rows / per_pass),
        effective_MBps=round(bytes_per_pass / per_pass / 1e6),
        n_iter=n_iter, init_sec=round(ph.get("init_centers", 0.0), 1),
        fit_sec=round(t_fit, 1),
        **_overlap_extras(m.summary.timings, "lloyd_loop"),
        **_compile_extras(m.summary.timings, "lloyd_loop",
                          getattr(m.summary, "progcache", None)),
        **_resilience_extras(m.summary),
        **_checkpoint_extras(m.summary),
    )
    # span-tree view of the same fit (telemetry/export.report): per-phase
    # walls, overlap, compile split — the human cross-check of the JSON
    from oap_mllib_tpu import telemetry

    print(telemetry.report(m.summary), flush=True)

    t0 = time.perf_counter()
    p = PCA(k=16).fit(src)
    t_fit_p = time.perf_counter() - t0
    assert p.summary["streamed"] and p.summary["n_rows"] == rows
    php = p.summary["timings"].as_dict()
    per_pass_p = php["covariance_streamed"] / 2  # two-pass centered Gram
    _emit(
        f"streamed_pca_{rows}x{d}_sec_per_pass",
        per_pass_p, "sec/pass", 1.0,
        effective_MBps=round(bytes_per_pass / per_pass_p / 1e6),
        eigh_sec=round(php.get("eigh", 0.0), 3),
        fit_sec=round(t_fit_p, 1),
        **_overlap_extras(p.summary["timings"], "covariance_streamed"),
        **_compile_extras(p.summary["timings"], "covariance_streamed",
                          p.summary.get("progcache")),
        **_resilience_extras(p.summary),
        **_checkpoint_extras(p.summary),
    )
    print(telemetry.report(p.summary), flush=True)


# ---------------------------------------------------------------------------
# Heterogeneous-fleet skew sweep (bench.py --skew, ISSUE 15)
# ---------------------------------------------------------------------------


def bench_skew(rows: int = 1 << 18, d: int = 64, k: int = 64,
               slow_factor: float = 4.0, emit: bool = True) -> dict:
    """Equal vs capability-weighted layout on a synthetically slowed
    rank (parallel/balance.py): a 2-rank world is SIMULATED in one
    process — each rank's Lloyd assignment pass walks its planned
    extent through the real per-chunk program, rank 1 paying a
    per-chunk sleep calibrated to ``slow_factor`` x the measured chunk
    time (a throttled host / CPU rank stand-in); the world's pass wall
    is the slowest rank's (the pass barrier).  Emits the
    ``hetero_speedup`` headline (equal wall / weighted wall — > 1 means
    the capability plan pays) plus both walls and the cross-layout
    parity, every line backend-tagged for dev/bench_regress.py's
    per-(metric, backend) gating."""
    from oap_mllib_tpu.data.stream import ChunkSource
    from oap_mllib_tpu.ops import stream_ops
    from oap_mllib_tpu.parallel import balance

    chunk = 1 << 13
    world = 2
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, d)).astype(np.float32)
    centers = np.ascontiguousarray(x[:k], np.float32)

    def _src(lo, n_loc, sleep_s):
        def gen():
            for s in range(lo, lo + n_loc, chunk):
                if sleep_s > 0:
                    time.sleep(sleep_s)
                yield x[s: s + min(chunk, lo + n_loc - s)]

        return ChunkSource(gen, d, chunk, n_rows=n_loc)

    def _pass(lo, n_loc, sleep_s):
        t0 = time.perf_counter()
        sums, counts, _ = stream_ops.streamed_accumulate(
            _src(lo, n_loc, sleep_s), centers, np.float32,
            "highest", need_cost=False,
        )
        return time.perf_counter() - t0, sums, counts

    # calibrate: one warm pass over an equal shard measures the real
    # per-chunk time; the slow rank then sleeps (slow_factor - 1) x that
    # per chunk — its effective throughput is 1/slow_factor
    half = (rows // 2 // chunk) * chunk
    _pass(0, half, 0.0)  # warm (compile)
    base_wall, _, _ = _pass(0, half, 0.0)
    per_chunk = base_wall / max(1, half // chunk)
    sleep_s = per_chunk * (slow_factor - 1.0)

    weights = {
        "equal": [1.0, 1.0],
        "weighted": [1.0, 1.0 / slow_factor],
    }
    walls = {}
    centers_out = {}
    for layout, w in weights.items():
        extents, _ = balance.plan_extents(rows, chunk, w)
        rank_walls = []
        agg_s = np.zeros((k, d), np.float32)
        agg_c = np.zeros((k,), np.float32)
        for r, (lo, n_loc) in enumerate(extents):
            if n_loc == 0:
                rank_walls.append(0.0)
                continue
            wall, sums, counts = _pass(
                lo, n_loc, sleep_s if r == 1 else 0.0
            )
            rank_walls.append(wall)
            agg_s += np.asarray(sums)
            agg_c += np.asarray(counts)
        walls[layout] = max(rank_walls)
        centers_out[layout] = agg_s / np.maximum(agg_c[:, None], 1e-30)
    speedup = walls["equal"] / max(walls["weighted"], 1e-9)
    parity = float(np.max(np.abs(
        centers_out["equal"] - centers_out["weighted"]
    )))
    out = {
        "hetero_speedup": round(speedup, 4),
        "equal_wall_s": round(walls["equal"], 4),
        "weighted_wall_s": round(walls["weighted"], 4),
        "parity": parity,
        "slow_factor": slow_factor,
    }
    if emit:
        _emit(
            "hetero_speedup", speedup, "x", 1.0,
            equal_wall_s=out["equal_wall_s"],
            weighted_wall_s=out["weighted_wall_s"],
            parity=round(parity, 8), slow_factor=slow_factor,
            rows=rows, d=d, world=world,
        )
        _emit("hetero_equal_wall", walls["equal"], "sec", 1.0,
              slow_factor=slow_factor, rows=rows, d=d)
        _emit("hetero_weighted_wall", walls["weighted"], "sec", 1.0,
              slow_factor=slow_factor, rows=rows, d=d)
    return out


# ---------------------------------------------------------------------------
# Compile-amortization size sweep (bench.py --compile-sweep)
# ---------------------------------------------------------------------------


def bench_compile_sweep(n_sizes: int = 10, d: int = 16, k: int = 8,
                        max_iter: int = 3, emit: bool = True) -> dict:
    """Fits at ``n_sizes`` distinct row counts (same d/k), shape
    bucketing off then on, counting REAL XLA backend compiles per fit
    (progcache.xla_compile_count — the monitoring-event ground truth,
    not the registry's opinion) and cross-checking per-fit parity
    between the two modes.

    Sizes are chosen so every fit has a DISTINCT exact-padded shape
    (one new compile set per fit with bucketing off — today's behavior)
    while all land in ONE geometric bucket (zero new compiles after the
    first fit with bucketing on).  The per-mode warm-up (first size) is
    reported separately from the steady tail, which is what the CI gate
    asserts on (dev/compile_gate.py).  Returns the result dict; with
    ``emit`` prints the usual one-line JSON.
    """
    from oap_mllib_tpu.config import get_config, set_config
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.parallel.mesh import get_mesh
    from oap_mllib_tpu.utils import progcache

    mesh = get_mesh()
    m0 = mesh.shape[mesh.axis_names[0]] * 256  # the table's pad multiple
    # sizes (16*m0, 32*m0]: exact pads (17..16+n)*m0 are all distinct,
    # the x2 bucket 32*m0 is shared — and is NOT any size's exact pad,
    # so the off sweep can never pre-compile the on sweep's program
    if n_sizes > 15:
        raise ValueError("n_sizes must be <= 15 (one x2 bucket spans 16)")
    sizes = [(16 + j) * m0 - 13 for j in range(1, n_sizes + 1)]
    rng = np.random.default_rng(11)
    x = rng.normal(size=(sizes[-1], d)).astype(np.float32) * 2.0

    prior = get_config().shape_bucketing
    out = {"sizes": sizes, "d": d, "k": k}
    centers = {}
    try:
        for mode in ("off", "on"):  # off FIRST (see sizes note above)
            set_config(shape_bucketing=mode)
            cache0 = progcache.stats()
            per_fit = []
            secs0 = progcache.xla_compile_secs()
            t0 = time.perf_counter()
            cents = []
            for n in sizes:
                c0 = progcache.xla_compile_count()
                model = KMeans(
                    k=k, seed=5, init_mode="random", max_iter=max_iter
                ).fit(x[:n])
                per_fit.append(progcache.xla_compile_count() - c0)
                cents.append(model.cluster_centers_)
            out[f"wall_sec_{mode}"] = round(time.perf_counter() - t0, 2)
            out[f"xla_compile_sec_{mode}"] = round(
                progcache.xla_compile_secs() - secs0, 2
            )
            out[f"compiles_{mode}"] = sum(per_fit)
            out[f"warm_compiles_{mode}"] = per_fit[0]
            out[f"steady_compiles_{mode}"] = sum(per_fit[1:])
            delta = progcache.delta(cache0)
            if delta.get("hit_rate") is not None:
                out[f"hit_rate_{mode}"] = round(delta["hit_rate"], 3)
            centers[mode] = cents
    finally:
        set_config(shape_bucketing=prior)

    # parity: same data, same seed — bucketing must not change the fit
    # (padding rows are weight-0; only summation order differs)
    out["parity_max_dev"] = float(
        max(
            np.abs(a - b).max()
            for a, b in zip(centers["off"], centers["on"])
        )
    )
    ratio = out["steady_compiles_off"] / max(out["steady_compiles_on"], 1)
    out["steady_compile_ratio"] = round(ratio, 2)

    # tuned leg: a pinned non-default walk geometry must ride the SAME
    # compile-amortization planes — the bucketed program cache within
    # the process (second same-bucket fit adds ZERO XLA compiles) and
    # the persistent XLA cache across processes (its executables land
    # on disk, so a warm restart skips backend compilation for tuned
    # programs exactly as it does for default-geometry ones).  The
    # cache is the process's one cache (use_checkout_cache): where the
    # environment or an earlier run already holds these programs, the
    # warm fit reads them instead of compiling, so the leg asserts on
    # what the cache HOLDS, not on what this run added.
    prior_tuning = get_config().tuning
    xdir = progcache.use_checkout_cache(_JAX_CACHE)
    try:
        set_config(
            shape_bucketing="on",
            tuning='pin:{"kmeans": {"tile_rows": 256, "depth": 3}}',
        )
        c0 = progcache.xla_compile_count()
        KMeans(k=k, seed=5, init_mode="random", max_iter=max_iter).fit(
            x[: sizes[0]]
        )
        out["tuned_warm_compiles"] = progcache.xla_compile_count() - c0
        c1 = progcache.xla_compile_count()
        KMeans(k=k, seed=5, init_mode="random", max_iter=max_iter).fit(
            x[: sizes[1]]  # distinct exact shape, same x2 bucket
        )
        out["tuned_steady_compiles"] = progcache.xla_compile_count() - c1
        out["tuned_cache_entries"] = sum(
            len(fs) for _, _, fs in os.walk(xdir)
        )
        assert out["tuned_steady_compiles"] == 0, (
            "pinned tuned geometry broke bucketed program reuse: "
            f"{out['tuned_steady_compiles']} new XLA compiles on the "
            "second same-bucket fit"
        )
        assert out["tuned_cache_entries"] > 0, (
            "tuned programs did not land in the persistent XLA "
            f"compilation cache at {xdir}"
        )
    finally:
        set_config(shape_bucketing=prior, tuning=prior_tuning)

    if emit:
        _emit(
            "kmeans_compile_sweep_10sizes", ratio, "x fewer XLA compiles",
            ratio, **{k2: v for k2, v in out.items() if k2 != "sizes"},
        )
    return out


# ---------------------------------------------------------------------------
# Mixed-precision policy sweep (bench.py --precision-sweep)
# ---------------------------------------------------------------------------


def bench_precision_sweep(emit: bool = True) -> dict:
    """Fit all three estimators under each compute-precision policy
    (utils/precision.py) on fixed seeds, reporting throughput
    (iters/sec for K-Means, fits/sec for PCA, iters/sec for ALS) AND
    parity vs the f32 policy — the same metrics dev/precision_gate.py
    asserts, recorded instead of gated, to show what each policy buys
    and costs on this backend.  CI-affordable shapes;
    on a real TPU the bf16 rows are the MFU-movers (half the operand
    HBM bytes, 2x MXU throughput)."""
    from oap_mllib_tpu.config import get_config, set_config
    from oap_mllib_tpu.models.als import ALS
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.models.pca import PCA
    from oap_mllib_tpu.utils.precision import TIERS

    rng = np.random.default_rng(17)
    n, d, k = 1 << 15, 64, 32
    proto = rng.normal(size=(k, d)).astype(np.float32) * 4.0
    x = (proto[rng.integers(k, size=n)]
         + rng.normal(size=(n, d)).astype(np.float32) * 0.3)
    nu, ni, nnz, rank = 1500, 900, 60_000, 8
    users = rng.integers(nu, size=nnz).astype(np.int64)
    items = rng.integers(ni, size=nnz).astype(np.int64)
    ratings = (rng.random(nnz) * 4 + 1).astype(np.float32)
    km_iters, als_iters = 10, 5
    scale = float(np.abs(x).max())

    prior = get_config().compute_precision
    out = {}
    ref = {}
    try:
        for pol in TIERS:  # f32 first: the parity reference
            set_config(compute_precision=pol)
            km = KMeans(k=k, seed=5, init_mode="random", max_iter=km_iters)
            t_km = _best_of(lambda: km.fit(x), reps=2)
            m = km.fit(x)
            t_pca = _best_of(lambda: PCA(k=8).fit(x), reps=2)
            p = PCA(k=8).fit(x)
            als = ALS(rank=rank, max_iter=als_iters, seed=3,
                      implicit_prefs=True, alpha=10.0)
            t_als = _best_of(lambda: als.fit(users, items, ratings), reps=2)
            a = als.fit(users, items, ratings)
            pred = a.predict(users[:2000], items[:2000])
            row = {
                "kmeans_iters_per_sec": round(
                    max(int(m.summary.num_iter), 1) / t_km, 3
                ),
                "pca_fits_per_sec": round(1.0 / t_pca, 3),
                "als_iters_per_sec": round(als_iters / t_als, 3),
                "policy_recorded": m.summary.precision,
            }
            if pol == "f32":
                ref = {
                    "centers": np.sort(m.cluster_centers_, axis=0),
                    "cost": m.summary.training_cost,
                    "pc": p.components_,
                    "pred": pred,
                }
            else:
                row["kmeans_centroid_rel_dev"] = float(
                    np.abs(
                        np.sort(m.cluster_centers_, axis=0) - ref["centers"]
                    ).max() / scale
                )
                row["kmeans_cost_rel_dev"] = float(
                    abs(m.summary.training_cost - ref["cost"])
                    / max(ref["cost"], 1e-30)
                )
                # principal-subspace angle via the singular values of
                # the cross-projection (order/sign-free)
                s = np.linalg.svd(ref["pc"].T @ p.components_,
                                  compute_uv=False)
                row["pca_subspace_rad"] = float(
                    np.arccos(np.clip(s.min(), 0.0, 1.0))
                )
                row["als_pred_rel_rmse"] = float(
                    np.sqrt(np.mean((pred - ref["pred"]) ** 2))
                    / max(float(np.sqrt(np.mean(ref["pred"] ** 2))), 1e-30)
                )
            out[pol] = row
            if emit:
                _emit(
                    "precision_sweep", row["kmeans_iters_per_sec"],
                    "kmeans iters/sec", 1.0, precision=pol,
                    **{k2: v for k2, v in row.items()
                       if k2 != "kmeans_iters_per_sec"},
                )
    finally:
        set_config(compute_precision=prior)
    return out


def bench_serving(requests: int = 200, sweep_users: int = 1_000_000,
                  emit: bool = True) -> dict:
    """Serving-plane bench (ISSUE 13): the BENCH JSON's second headline
    next to iters/sec.

    Leg 1 — request storm: a served K-Means model answers ``requests``
    jittered-size batches after a bucket-family warmup; reports
    sustained QPS, p50/p99 tail latency (per-request walls, host
    round-trip included), rows/sec, and the steady-state XLA compile
    count (MUST be zero — ground truth via xla_compile_count).

    Leg 2 — full-sweep top-k: ``recommend_for_all_users`` over a
    ``sweep_users``-row synthetic factor table through the streamed,
    prefetch-pipelined sweep (serving/sweep.py) — users/sec with the
    quadratic score matrix never materialized.

    Leg 3 — multi-process fleet storm (ISSUE 16): a REAL 2-replica
    world (tests/pseudo_cluster_worker_traffic.py, bench mode) drives
    sustained jittered storms through each replica's async
    TrafficQueue; the ``serving_kmeans_qps_mp`` headline is the
    fleet-aggregate QPS.  Hosts that cannot spawn a multiprocess jax
    world WARN and skip the leg (bench_regress is name-keyed and
    warn-skips absent metrics)."""
    import numpy as np

    from oap_mllib_tpu import serving
    from oap_mllib_tpu.models.als import ALSModel
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.serving import sweep as sweep_mod
    from oap_mllib_tpu.utils import progcache

    rng = np.random.default_rng(7)
    d, k, max_rows = 64, 64, 2048
    x = rng.normal(size=(max_rows * 2, d)).astype(np.float32)
    model = KMeans(k=k, seed=0, init_mode="random", max_iter=3).fit(x)
    handle = serving.serve(model)
    handle.warmup(max_rows)
    sizes = rng.integers(1, max_rows, size=requests)
    before = progcache.xla_compile_count()
    walls = []
    t0 = time.perf_counter()
    for s in sizes:
        t1 = time.perf_counter()
        handle.predict(x[: int(s)])
        walls.append(time.perf_counter() - t1)
    storm_wall = time.perf_counter() - t0
    steady_compiles = progcache.xla_compile_count() - before
    walls.sort()
    p50 = walls[len(walls) // 2]
    p99 = walls[min(len(walls) - 1, int(len(walls) * 0.99))]
    qps = requests / storm_wall
    rows = int(np.sum(sizes))
    block = serving.serving_summary()
    attribution = _bench_serving_attribution(handle, x, sizes)
    if emit:
        _emit(
            "serving_kmeans_qps", qps, "req/sec", 0.0,
            p50_ms=round(p50 * 1e3, 3), p99_ms=round(p99 * 1e3, 3),
            rows_per_sec=round(rows / storm_wall, 1),
            steady_compiles=steady_compiles,
            pad_rows=block["pad_rows"], requests=requests,
            batch_d=d, batch_k=k, **attribution,
        )

    nu, ni, r, topk = int(sweep_users), 256, 16, 10
    uf = rng.normal(size=(nu, r)).astype(np.float32)
    itf = rng.normal(size=(ni, r)).astype(np.float32)
    als = ALSModel(uf, itf)
    t0 = time.perf_counter()
    ids = sweep_mod.recommend_for_all_users(als, topk)
    sweep_wall = time.perf_counter() - t0
    assert ids.shape == (nu, topk)
    users_per_sec = nu / sweep_wall
    if emit:
        _emit(
            "serving_als_sweep_users_per_sec", users_per_sec,
            "users/sec", 0.0,
            sweep_users=nu, n_items=ni, rank=r, top_k=topk,
            sweep_wall_sec=round(sweep_wall, 2),
        )
    # the brownout + fleet legs only price into emitting runs —
    # in-process callers (dev/serve_gate.py leg 5) measure the
    # single-process storm only
    bo = _bench_serving_brownout(handle, x, sizes, emit) if emit else None
    mp = bench_serving_mp(emit=True) if emit else None
    return {
        "qps": qps, "p50_s": p50, "p99_s": p99,
        "steady_compiles": steady_compiles,
        "users_per_sec": users_per_sec,
        "qps_brownout": None if bo is None else bo["qps"],
        "qps_mp": None if mp is None else mp["qps_mp"],
    }


def _bench_serving_attribution(handle, x, sizes) -> dict:
    """Deadline-budget attribution fields for the ``--serving`` line
    (ISSUE 19): a short traced storm through the async TrafficQueue
    (``serve_trace_sample=1.0``) whose per-stage p99s say where a
    request's wall goes — fields are name-keyed extras, so
    dev/bench_regress.py picks them up with no changes."""
    from oap_mllib_tpu.config import get_config, set_config
    from oap_mllib_tpu.serving import reqtrace, traffic as traffic_mod

    prev = float(get_config().serve_trace_sample)
    n = min(100, len(sizes))
    set_config(serve_trace_sample=1.0)
    try:
        with traffic_mod.TrafficQueue(handle) as q:
            futs = [
                q.submit(x[: int(s)], deadline_ms=0.0)
                for s in sizes[:n]
            ]
            for f in futs:
                f.result(timeout=60)
        sq = reqtrace.stage_quantiles()
    finally:
        set_config(serve_trace_sample=prev)

    def p99_ms(stage: str) -> float:
        return round(sq.get(stage, {}).get("p99_s", 0.0) * 1e3, 3)

    return {
        "queue_wait_p99_ms": p99_ms("queue_wait"),
        "batch_form_p99_ms": p99_ms("batch_form"),
        "execute_p99_ms": p99_ms("execute"),
    }


def _bench_serving_brownout(handle, x, sizes, emit: bool) -> dict:
    """Degraded-mode headline (ISSUE 18): the same jittered storm
    through the async TrafficQueue with the brownout ladder pinned at
    its top rung (reduced top-k + bf16 + stale pins all active), two
    transient dispatcher faults armed (the retry envelope), and two
    NaN-payload requests (poison bisection) — ``serving_kmeans_qps_
    brownout`` is the throughput a browned-out replica still sustains,
    with the retry/poison counters it booked along the way."""
    import numpy as np

    from oap_mllib_tpu import serving
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.serving import traffic as traffic_mod
    from oap_mllib_tpu.telemetry import metrics as tm

    requests = len(sizes)
    retries0 = int(tm.family_total("oap_serve_retries_total"))
    poison0 = int(tm.family_total("oap_serve_poison_total"))
    try:
        set_config(serve_brownout="pin:stale",
                   fault_spec="serve.dispatch:fail=2")
        traffic_mod._reset_for_tests()
        # the degraded precision policy (bf16 rung) compiles its own
        # bucket family — warm it so the storm stays compile-free
        handle.warmup(2048)
        nan_at = {3, requests // 2}
        reqs = []
        for i, s in enumerate(sizes):
            b = x[: int(s)]
            if i in nan_at:
                b = b.copy()
                b[0, 0] = np.nan
            reqs.append(b)
        walls = []
        t0 = time.perf_counter()
        with serving.TrafficQueue(handle) as q:
            futs = [
                (time.perf_counter(), q.submit(b, deadline_ms=120_000))
                for b in reqs
            ]
            for ts, f in futs:
                try:
                    f.result(timeout=120)
                except serving.ServeError:
                    pass  # the quarantined poison payloads
                walls.append(time.perf_counter() - ts)
        storm_wall = time.perf_counter() - t0
    finally:
        set_config(serve_brownout="auto", fault_spec="")
        traffic_mod._reset_for_tests()
    walls.sort()
    p50 = walls[len(walls) // 2]
    p99 = walls[min(len(walls) - 1, int(len(walls) * 0.99))]
    qps = requests / storm_wall
    retried = int(tm.family_total("oap_serve_retries_total")) - retries0
    poison = int(tm.family_total("oap_serve_poison_total")) - poison0
    if emit:
        _emit(
            "serving_kmeans_qps_brownout", qps, "req/sec", 0.0,
            p50_ms=round(p50 * 1e3, 3), p99_ms=round(p99 * 1e3, 3),
            rung="stale", requests=requests,
            retried=retried, poison=poison,
        )
    return {"qps": qps, "retried": retried, "poison": poison}


# environment-incapability signatures (mirrors tests/test_pseudo_cluster
# .py): a worker that died on one of these means this HOST cannot form
# a multiprocess jax world — warn + skip, not a bench failure
_MP_ENV_FAILURE_MARKERS = (
    "Multiprocess computations aren't implemented",
    "UNIMPLEMENTED",
    "Unable to initialize backend",
    "failed to join world",
    "DEADLINE_EXCEEDED",
    "Failed to connect to coordinator",
)


def bench_online(new_users: int = 10_000, emit: bool = True) -> dict:
    """Online-learning bench (ISSUE 20): the delta-commit headline.

    Folds ``new_users`` brand-new users (6 ratings each) into a fitted
    ALS model through the batched fold-in solve (online/foldin.py) and
    prices it against the nightly-refit alternative: a full
    from-scratch fit on base + delta at the same max_iter.  A small
    warming delta compiles the bucketed solve first, so the timed
    commit is the steady state a live service pays per delta.

    Emits ``als_foldin_users_per_sec`` and ``online_speedup_vs_refit``
    (refit wall / fold-in wall; the acceptance bound at this scale is
    >= 20x).  The prediction-space parity of the folded rows vs the
    refit (rel Frobenius over the grown rows' score vectors — factor
    rows are only unique up to an invertible transform, so
    prediction space is the meaningful comparison; documented bound
    0.15, docs/user-guide.md) rides both lines."""
    from oap_mllib_tpu.models.als import ALS

    rng = np.random.default_rng(15)
    nu, ni, rank, nnz = 20_000, 500, 8, 300_000
    u = rng.integers(0, nu, size=nnz)
    i = rng.integers(0, ni, size=nnz)
    r = rng.normal(1.0, 0.5, size=nnz).astype(np.float32)
    est = dict(rank=rank, max_iter=5, reg_param=0.1, seed=6,
               num_user_blocks=1)
    base = ALS(**est).fit(u, i, r, n_users=nu, n_items=ni)

    def _delta(lo, n):
        du = np.repeat(np.arange(lo, lo + n), 6)
        di = rng.integers(0, ni, size=du.size).astype(np.int64)
        dr = rng.normal(1.0, 0.5, size=du.size).astype(np.float32)
        return du, di, dr

    # warming delta in the SAME power-of-two shape buckets as the
    # timed one (edges and destination rows both land one bucket)
    warm_n = max(1, int(new_users * 0.9))
    du1, di1, dr1 = _delta(nu, warm_n)
    du2, di2, dr2 = _delta(nu + warm_n, new_users)
    base.fold_in_users(du1, di1, dr1)
    t0 = time.perf_counter()
    base.fold_in_users(du2, di2, dr2)
    foldin_wall = time.perf_counter() - t0

    t0 = time.perf_counter()
    refit = ALS(**est).fit(
        np.concatenate([u, du1, du2]), np.concatenate([i, di1, di2]),
        np.concatenate([r, dr1, dr2]),
        n_users=nu + warm_n + new_users, n_items=ni,
    )
    refit_wall = time.perf_counter() - t0

    pred_fold = base.user_factors_[nu:] @ base.item_factors_.T
    pred_refit = refit.user_factors_[nu:] @ refit.item_factors_.T
    parity = float(np.linalg.norm(pred_fold - pred_refit)
                   / np.linalg.norm(pred_refit))
    users_per_sec = new_users / foldin_wall
    speedup = refit_wall / max(foldin_wall, 1e-9)
    extra = dict(
        new_users=new_users, rank=rank, n_items=ni,
        foldin_wall_sec=round(foldin_wall, 4),
        refit_wall_sec=round(refit_wall, 2),
        parity_rel_frobenius=round(parity, 4),
    )
    if emit:
        # vs_baseline IS the refit: the delta path's win over the
        # nightly full-refit pattern it replaces (docs/migration.md)
        _emit("als_foldin_users_per_sec", users_per_sec, "users/sec",
              speedup, **extra)
        _emit("online_speedup_vs_refit", speedup, "x", speedup, **extra)
    return {
        "users_per_sec": users_per_sec, "speedup": speedup,
        "parity": parity, "foldin_wall": foldin_wall,
        "refit_wall": refit_wall,
    }


def bench_serving_mp(nproc: int = 2, requests: int = 200,
                     emit: bool = True):
    """Fleet-QPS line: spawn ``nproc`` bench-mode traffic workers as a
    real multi-process world, parse each replica's ``BENCH_QPS`` line,
    and emit the aggregate as ``serving_kmeans_qps_mp``.  The workers
    are PINNED TO THE CPU (``JAX_PLATFORMS=cpu`` in their environment):
    the chip belongs to this process, and a child that needed it would
    fail or hang — so whatever backend the parent runs on, this line is
    a CPU-world protocol number and carries ``backend_workers: "cpu"``.
    Returns None (after a WARN) when this host cannot spawn the world
    — the regression harness warn-skips metrics absent from a run."""
    import subprocess
    import tempfile

    from oap_mllib_tpu.parallel.bootstrap import free_port

    repo = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(repo, "tests", "pseudo_cluster_worker_traffic.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["JAX_PLATFORMS"] = "cpu"  # never the parent's chip (see docstring)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["TRAFFIC_WORKER_MODE"] = "bench"
    env["TRAFFIC_BENCH_REQUESTS"] = str(requests)
    with tempfile.TemporaryDirectory() as crash_dir:
        env["TRAFFIC_CRASH_DIR"] = crash_dir
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
                out, _ = p.communicate(timeout=300)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    per_rank = []
    for p, out in zip(procs, outs):
        if any(m in out for m in _MP_ENV_FAILURE_MARKERS):
            print("WARN: serving_kmeans_qps_mp skipped — this host "
                  "cannot form a multiprocess jax world",
                  file=sys.stderr)
            return None
        if p.returncode != 0:
            print("WARN: serving_kmeans_qps_mp skipped — bench worker "
                  f"exited {p.returncode}:\n{out[-1500:]}",
                  file=sys.stderr)
            return None
        line = [ln for ln in out.splitlines()
                if ln.startswith("BENCH_QPS ")]
        if not line:
            print("WARN: serving_kmeans_qps_mp skipped — no BENCH_QPS "
                  f"line:\n{out[-1500:]}", file=sys.stderr)
            return None
        per_rank.append(
            dict(kv.split("=", 1) for kv in line[-1].split()[1:])
        )
    # every replica stormed concurrently: the fleet answers the SUM of
    # the per-replica rates; the tail is the worst replica's tail
    qps_mp = sum(float(r["qps"]) for r in per_rank)
    p50_ms = max(float(r["p50_ms"]) for r in per_rank)
    p99_ms = max(float(r["p99_ms"]) for r in per_rank)
    if emit:
        _emit(
            "serving_kmeans_qps_mp", qps_mp, "req/sec", 0.0,
            nproc=nproc, requests_per_replica=requests,
            backend_workers="cpu",
            per_replica_qps=[round(float(r["qps"]), 1) for r in per_rank],
            p50_ms=round(p50_ms, 3), p99_ms=round(p99_ms, 3),
        )
    return {"qps_mp": qps_mp, "p50_ms": p50_ms, "p99_ms": p99_ms}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--all", action="store_true",
                    help="emit every single-chip metric (one JSON line each)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="weak-scaling harness over 1..N ranks "
                         "(virtual CPU devices unless --mesh-backend real)")
    ap.add_argument("--mesh-backend", choices=("cpu", "real"), default="cpu",
                    help="cpu: force an N-device virtual CPU mesh (protocol "
                         "check, not ICI scaling); real: use the live "
                         "backend's devices (a TPU slice)")
    ap.add_argument("--mesh-sizes", choices=("small", "big"), default="small",
                    help="per-rank work: small = CI-affordable, big = "
                         "slice-scale shapes")
    ap.add_argument("--streamed", type=int, default=0, metavar="ROWS",
                    help="north-star streamed scale: generator-backed "
                         "K-Means + PCA at ROWS x 256 (100000000 = the "
                         "full BASELINE.json config on a pod host)")
    ap.add_argument("--compile-sweep", action="store_true",
                    help="compile-amortization sweep: K-Means fits at 10 "
                         "distinct row counts, shape bucketing off vs on, "
                         "counting real XLA compiles + checking parity")
    ap.add_argument("--precision-sweep", action="store_true",
                    help="mixed-precision policy sweep: the three "
                         "estimators under f32/tf32/bf16, reporting "
                         "throughput + parity vs f32 per policy")
    ap.add_argument("--skew", action="store_true",
                    help="heterogeneous-fleet sweep: equal vs "
                         "capability-weighted layout on a synthetically "
                         "slowed rank (simulated 2-rank world), emitting "
                         "the hetero_speedup headline + parity")
    ap.add_argument("--skew-factor", type=float, default=4.0,
                    metavar="X",
                    help="how many times slower the synthetic straggler "
                         "runs (default 4.0)")
    ap.add_argument("--online", action="store_true",
                    help="online-learning plane: ALS fold-in of 10k new "
                         "users vs a full refit on the same container "
                         "(als_foldin_users_per_sec + "
                         "online_speedup_vs_refit, prediction-space "
                         "parity riding the lines)")
    ap.add_argument("--serving", action="store_true",
                    help="serving plane: sustained QPS + p50/p99 tail "
                         "latency on a jittered request storm (zero "
                         "steady-state compiles) and full-sweep top-k "
                         "users/sec on a 1M-user synthetic factor table")
    args = ap.parse_args()

    if args.serving and "locks" in _sanitizers_state():
        # same policy as the sweep refusals below: the locks sanitizer
        # adds per-acquisition bookkeeping on the serving registry and
        # telemetry seams, so a QPS/tail-latency headline under it is
        # not comparable to the locks-off baselines
        ap.error(
            f"--serving refuses to run with the locks sanitizer armed "
            f"(Config.sanitizers={_sanitizers_state()!r}): tracked-lock "
            "bookkeeping inflates request tail latency, so the QPS/p99 "
            "headline would not be comparable to locks-off baselines; "
            "unset OAP_MLLIB_TPU_SANITIZERS for benching"
        )

    if (args.precision_sweep or args.compile_sweep) \
            and _sanitizers_state() != "off":
        # the sweeps are compile-count/throughput COMPARISONS — within
        # the run (bucketing off vs on, f32 vs bf16) and against the
        # BENCH_r* baselines, all recorded sanitizers-off.  The
        # collective sanitizer adds a gather per host collective and the
        # retrace guard perturbs compile accounting, so a sweep under a
        # different sanitizer set is not comparable: refuse instead of
        # emitting silently skewed numbers.
        ap.error(
            f"--precision-sweep/--compile-sweep refuse to run with "
            f"sanitizers armed (Config.sanitizers="
            f"{_sanitizers_state()!r}): sanitizers perturb compile "
            "counts and collective walls, so the sweep would not be "
            "comparable to sanitizers-off baselines; unset "
            "OAP_MLLIB_TPU_SANITIZERS for benching"
        )

    if args.precision_sweep:
        bench_precision_sweep()
        return

    if args.online:
        bench_online()
        return

    if args.serving:
        bench_serving()
        return

    if args.compile_sweep:
        bench_compile_sweep()
        return

    if args.skew:
        if args.skew_factor <= 1.0:
            ap.error("--skew-factor must be > 1.0")
        bench_skew(slow_factor=args.skew_factor)
        return

    if args.streamed:
        bench_streamed(args.streamed)
        return

    if args.mesh:
        if args.mesh_backend == "cpu":
            # must happen before any backend initializes
            import jax

            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", args.mesh)
        bench_mesh(args.mesh, args.mesh_backend, args.mesh_sizes)
        return

    extra = {}
    _peak_flops()  # the headline run measures a chip: fail at once without

    from oap_mllib_tpu.config import get_config
    from oap_mllib_tpu.utils import precision as psn, progcache

    progcache.use_checkout_cache(_JAX_CACHE)

    # The compute-precision POLICY resolves first (Config
    # .compute_precision / kmeans_precision — utils/precision.py): a
    # reduced policy maps the kernel tier itself and is what the JSON's
    # `precision` field records.  Under the default f32 policy the
    # headline tier stays "high" — bf16_3x sums + bf16 assignment,
    # validated within the 1e-4 parity bar by tests_tpu (a command of
    # its own: a child that needs the chip cannot start from a parent
    # that holds it) — and an explicit env override of matmul_precision
    # still wins.
    pol = psn.resolve("kmeans")
    if pol.name != "f32":
        precision = psn.kernel_tier(pol.name, get_config().matmul_precision)
    else:
        precision = (
            get_config().matmul_precision
            if "OAP_MLLIB_TPU_MATMUL_PRECISION" in os.environ
            else "high"
        )
    if args.all:
        _, cpu_ips = bench_kmeans("high", extra=extra, policy=pol.name)
        bench_kmeans("highest", cpu_ips=cpu_ips, policy=pol.name)
        bench_pca(n=1 << 20, d=128)
        bench_pca(n=1 << 17, d=2048)  # largest-d single-chip proxy
        bench_als()
        bench_als_large()
    else:
        # the default (driver-captured) run emits ONE bound-annotated
        # headline per algorithm: K-Means MFU vs
        # bf16 peak, PCA covariance TFLOP/s + eigh wall share, ALS
        # gather indices/s vs the measured ~250M/s ceiling — so a
        # regression in ANY algorithm surfaces in BENCH_r<NN>.json.
        # (--all adds the d=2048 PCA proxy and the ML-25M ALS scale.)
        bench_kmeans(precision, extra=extra, policy=pol.name)
        bench_pca(n=1 << 20, d=128)
        bench_als()


if __name__ == "__main__":
    main()
