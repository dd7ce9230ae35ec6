#!/usr/bin/env python
"""K-Means kernel shoot-out: chunked-XLA Lloyd vs fused Pallas, per shape
and precision tier, on the current backend.

Emits one JSON line per (shape, tier, kernel) plus a markdown table —
the evidence Config.kmeans_kernel="auto" (kmeans_ops.pallas_preferred)
is held to; regenerate with ``python dev/profile_kernels.py`` on TPU.

Timing method: per-iteration SLOPE between a short and a long jitted
Lloyd run (the slope cancels the per-call dispatch latency).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SHAPES = [
    # (n, d, k) — bench headline, smaller-k, high-d, small
    (1 << 20, 256, 1000),
    (1 << 20, 64, 128),
    (1 << 18, 1024, 256),
    (1 << 16, 64, 64),
]
TIERS = ["highest", "high", "default"]


def _iter_window(flops_per_iter: float) -> tuple:
    """(short, long) iteration counts sized so the slope window holds >= ~2s
    of assumed-30TFLOP/s work — small shapes at 4..16 iters complete in
    tens of ms and the host's per-call jitter swamps the slope."""
    long = int(max(16, min(1024, 2.0 * 30e12 / flops_per_iter)))
    return max(4, long // 4), long


def _time_run(fn):
    fn()  # compile + warm the exact variant
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def profile():
    import jax.numpy as jnp

    from oap_mllib_tpu.ops import kmeans_ops
    from oap_mllib_tpu.ops.pallas.kmeans_kernel import lloyd_run_pallas

    rows = []
    for n, d, k in SHAPES:
        # UNIFORM random data + random init: Lloyd must not converge inside
        # the timed window, or the short/long runs do identical work and
        # the slope is noise (blob data converges in a handful of iters)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.ones((n,), jnp.float32)
        c0 = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        tol = jnp.asarray(0.0, jnp.float32)
        chunks = kmeans_ops.auto_row_chunks(n, k)
        flops = 2 * 2 * n * k * d
        window = _iter_window(flops)

        for tier in TIERS:
            per = {}
            for kernel in ("xla", "pallas"):
                ts = {}
                win = window
                for attempt in range(3):
                    ok = True
                    for iters in win:
                        if kernel == "xla":
                            run = lambda it=iters: kmeans_ops.lloyd_run(
                                x, w, c0, it, tol, chunks, tier
                            )
                        else:
                            run = lambda it=iters: lloyd_run_pallas(
                                x, w, c0, it, tol, mode=tier
                            )
                        n_iter = int(run()[1])
                        if n_iter != iters:
                            # Lloyd hit an exact fixed point before the
                            # window closed (zero moves satisfy tol=0):
                            # shrink the window below the convergence
                            # point and retry instead of aborting
                            win = (max(2, n_iter // 8), max(8, n_iter // 2))
                            ok = False
                            break
                        fn = lambda r=run, it=iters: np.asarray(r(it)[0])
                        ts[iters] = _time_run(fn)
                    if ok:
                        break
                else:
                    print(f"# skip {n}x{d} k={k} {tier} {kernel}: converges "
                          "too fast for a stable slope", flush=True)
                    continue
                per[kernel] = (ts[win[1]] - ts[win[0]]) / (win[1] - win[0])
                if per[kernel] <= 0:
                    # long run timed faster than short: per-iteration cost
                    # is below the host's jitter floor — unreportable
                    print(f"# skip {n}x{d} k={k} {tier} {kernel}: below "
                          "slope resolution", flush=True)
                    del per[kernel]
                    continue
                rows.append({
                    "shape": f"{n}x{d} k={k}", "tier": tier, "kernel": kernel,
                    "ms_per_iter": round(per[kernel] * 1e3, 2),
                    "iters_per_sec": round(1 / per[kernel], 1),
                    "tflops": round(flops / per[kernel] / 1e12, 1),
                })
                print(json.dumps(rows[-1]), flush=True)
    return rows


def markdown(rows) -> str:
    out = [
        "| shape | tier | XLA ms/iter | Pallas ms/iter | winner |",
        "|---|---|---|---|---|",
    ]
    by = {}
    for r in rows:
        by.setdefault((r["shape"], r["tier"]), {})[r["kernel"]] = r["ms_per_iter"]
    for (shape, tier), d in by.items():
        if "xla" in d and "pallas" in d:
            win = "xla" if d["xla"] <= d["pallas"] else "pallas"
            out.append(
                f"| {shape} | {tier} | {d['xla']} | {d['pallas']} | {win} |"
            )
    return "\n".join(out)


ALS_SHAPES = [
    # (n_users, n_items, nnz, rank) — MovieLens-1M scale + a small shape
    (6040, 3706, 1 << 20, 10),
    (1000, 800, 1 << 17, 10),
]


def profile_als():
    """ALS normal-equation shoot-out: grouped-edge vs COO per-iteration
    slope (implicit mode, the reference's accelerated surface) — the
    evidence behind Config.als_kernel="auto" preferring the grouped
    layout."""
    import jax.numpy as jnp

    from oap_mllib_tpu.ops import als_ops

    rows = []
    for nu, ni, nnz, rank in ALS_SHAPES:
        rng = np.random.default_rng(0)
        u = rng.integers(0, nu, nnz).astype(np.int32)
        i = rng.integers(0, ni, nnz).astype(np.int32)
        r = (rng.random(nnz) * 4 + 1).astype(np.float32)
        x0 = jnp.asarray((rng.normal(size=(nu, rank)) * 0.1).astype(np.float32))
        y0 = jnp.asarray((rng.normal(size=(ni, rank)) * 0.1).astype(np.float32))
        pad = (-nnz) % 2048
        uj = jnp.asarray(np.pad(u, (0, pad)))
        ij = jnp.asarray(np.pad(i, (0, pad)))
        rj = jnp.asarray(np.pad(r, (0, pad)))
        vj = jnp.asarray(np.pad(np.ones(nnz, np.float32), (0, pad)))
        by_u = tuple(jnp.asarray(a) for a in als_ops.build_grouped_edges(u, i, r, nu))
        by_i = tuple(jnp.asarray(a) for a in als_ops.build_grouped_edges(i, u, r, ni))

        def run_grouped(iters):
            return als_ops.als_run_grouped(
                *by_u, *by_i, x0, y0, nu, ni, iters, 0.1, 40.0, True
            )

        def run_coo(iters):
            return als_ops.als_implicit_run(
                uj, ij, rj, vj, x0, y0, nu, ni, iters, 0.1, 40.0
            )

        for kernel, run in (("grouped", run_grouped), ("coo", run_coo)):
            # calibrate the slope window to >= ~2s of work (same rationale
            # as _iter_window: a hardcoded short window leaves fast shapes
            # at the host's dispatch-jitter floor).  The
            # estimate is itself a SLOPE — whole-call time divided by
            # iterations would fold the fixed per-call dispatch overhead
            # into the per-iteration cost and undershoot the window on
            # exactly the fast shapes this calibration exists for.
            fn4 = lambda r_=run: np.asarray(r_(4)[0])
            fn16 = lambda r_=run: np.asarray(r_(16)[0])
            est = max((_time_run(fn16) - _time_run(fn4)) / 12, 1e-4)
            long = int(max(16, min(2048, 2.0 / est)))
            win = (max(4, long // 4), long)
            ts = {}
            for iters in win:
                fn = lambda it=iters, r_=run: np.asarray(r_(it)[0])
                ts[iters] = _time_run(fn)
            slope = (ts[win[1]] - ts[win[0]]) / (win[1] - win[0])
            if slope <= 0:
                print(f"# skip als {nu}x{ni} nnz={nnz} {kernel}: below "
                      "slope resolution", flush=True)
                continue
            rows.append({
                "shape": f"{nu}x{ni} nnz={nnz} r={rank}",
                "kernel": kernel,
                "ms_per_iter": round(slope * 1e3, 2),
            })
            print(json.dumps(rows[-1]), flush=True)
    return rows


def markdown_als(rows) -> str:
    out = [
        "| shape | grouped ms/iter | COO ms/iter | speedup |",
        "|---|---|---|---|",
    ]
    by = {}
    for r in rows:
        by.setdefault(r["shape"], {})[r["kernel"]] = r["ms_per_iter"]
    for shape, d in by.items():
        if "grouped" in d and "coo" in d:
            # a positive slope can still round to 0.00 ms; don't let the
            # speedup column kill the table after a multi-minute bench
            ratio = (
                f"{d['coo'] / d['grouped']:.1f}×" if d["grouped"] > 0 else "—"
            )
            out.append(
                f"| {shape} | **{d['grouped']}** | {d['coo']} | {ratio} |"
            )
    return "\n".join(out)


PCA_SHAPES = [
    # (n, d) — streamed-chunk scale + the large-d wall
    (1 << 18, 256),
    (1 << 16, 1024),
]
SOLVE_SHAPES = [
    # (n_dst, rank) — ML-1M user side + a wide batch
    (6040, 10),
    (200_000, 10),
]


def profile_fused():
    """Fused-vs-unfused shoot-out for the ISSUE 9 kernels: the PCA
    covariance pass (XLA two-pass vs the fused Pallas moments kernel)
    and the ALS batched normal-equation solve (XLA unrolled batch solve
    vs the fused Pallas assembly+solve).  Off-TPU the Pallas legs run in
    interpret mode — parity-only, timings meaningless — so regenerate on
    hardware like the K-Means table."""
    import jax
    import jax.numpy as jnp

    from oap_mllib_tpu.ops import als_ops
    from oap_mllib_tpu.ops.pallas.als_kernel import solve_normal_eq_pallas
    from oap_mllib_tpu.ops.pallas.pca_kernel import covariance_pallas
    from oap_mllib_tpu.ops.pca_ops import _covariance_jit

    interp = jax.default_backend() != "tpu"
    pca_shapes, solve_shapes = PCA_SHAPES, SOLVE_SHAPES
    if interp:
        print("# non-TPU backend: pallas legs run interpret mode on "
              "reduced shapes (parity only — timings not comparable)",
              flush=True)
        pca_shapes, solve_shapes = [(4096, 128)], [(6040, 10)]
    rows = []
    rng = np.random.default_rng(0)
    for n, d in pca_shapes:
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        m = jnp.ones((n,), jnp.float32)
        nv = jnp.asarray(float(n))
        for kernel, run in (
            ("xla", lambda: np.asarray(_covariance_jit(x, m, nv)[0])),
            ("pallas", lambda: np.asarray(
                covariance_pallas(x, m, nv, interpret=interp)[0])),
        ):
            dt = _time_run(run)
            flops = 2 * n * d * d  # centered Gram
            rows.append({
                "op": "pca_covariance", "shape": f"{n}x{d}",
                "kernel": kernel, "ms": round(dt * 1e3, 2),
                "tflops": round(flops / dt / 1e12, 2),
            })
            print(json.dumps(rows[-1]), flush=True)
    for nd, r in solve_shapes:
        mm = rng.normal(size=(nd, r, r)).astype(np.float32)
        a = jnp.asarray(np.einsum("nij,nkj->nik", mm, mm) + 0.5 * np.eye(r))
        b = jnp.asarray(rng.normal(size=(nd, r)).astype(np.float32))
        n_reg = jnp.asarray(np.ones((nd,), np.float32))
        gram = jnp.asarray(np.eye(r, dtype=np.float32))
        eye = jnp.eye(r, dtype=jnp.float32)
        solve = jax.jit(
            lambda a_, b_, n_: als_ops.regularized_solve(
                a_, b_, n_, 0.1, eye, gram
            )
        )
        for kernel, run in (
            ("xla", lambda: np.asarray(solve(a, b, n_reg))),
            ("pallas", lambda: np.asarray(solve_normal_eq_pallas(
                a, b, n_reg, 0.1, gram, interpret=interp))),
        ):
            dt = _time_run(run)
            rows.append({
                "op": "als_solve", "shape": f"{nd}xr{r}",
                "kernel": kernel, "ms": round(dt * 1e3, 2),
            })
            print(json.dumps(rows[-1]), flush=True)
    return rows


def profile_overlap():
    """Ring-overlap on/off sweep: per-iteration slope of the
    model-sharded Lloyd with the ring-fused moments reduction vs the
    psum path, on whatever mesh the backend offers (the 8-device virtual
    CPU mesh exercises the schedule; ICI overlap numbers need TPU)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.ops import kmeans_ops
    from oap_mllib_tpu.parallel.mesh import get_mesh

    if len(jax.devices()) < 2:
        print("# <2 devices: ring == psum fallback, nothing to sweep",
              flush=True)
        return []
    set_config(model_parallel=1)
    mesh = get_mesh()
    rng = np.random.default_rng(0)
    n, d, k = 1 << 17, 128, 128
    x = rng.normal(size=(n, d)).astype(np.float32)
    xs = jax.device_put(
        jnp.asarray(x), NamedSharding(mesh, P("data", "model"))
    )
    ws = jax.device_put(
        jnp.ones((n,), jnp.float32), NamedSharding(mesh, P("data"))
    )
    tol = jnp.asarray(0.0, jnp.float32)
    rows = []
    for mode in ("auto", "off"):
        set_config(ring_reduction=mode)
        ts = {}
        for iters in (4, 16):
            fn = lambda it=iters: np.asarray(
                kmeans_ops.lloyd_run_model_sharded(
                    xs, ws, jnp.asarray(x[:k]), it, tol, mesh,
                    "data", "model",
                )[0]
            )
            ts[iters] = _time_run(fn)
        slope = (ts[16] - ts[4]) / 12
        rows.append({
            "op": "lloyd_model_sharded", "ring": mode,
            "shape": f"{n}x{d} k={k}",
            "ms_per_iter": round(max(slope, 0.0) * 1e3, 2),
        })
        print(json.dumps(rows[-1]), flush=True)
    set_config(ring_reduction="auto")
    return rows


SWEEP_BUCKETS = {
    # representative bucket dims per kernel family: (k, d) for kmeans,
    # (d,) for pca, (r,) for the ALS kernels — buckets are n-independent
    # (ops/pallas/autotune.shape_bucket), so one bucket per family shows
    # the whole geometry response
    "kmeans": (128, 256),
    "pca": (256,),
    "als_gram": (16,),
    "als_solve": (16,),
}


def profile_sweep():
    """Autotuner candidate-grid shoot-out (ops/pallas/autotune.py): time
    EVERY candidate geometry per kernel family at a representative shape
    bucket through the tuner's own measurement harness — the long-form
    evidence behind each cached winner.  Off-TPU the kernels run in
    interpret mode (structure-only; regenerate on hardware like the
    other tables)."""
    import jax

    from oap_mllib_tpu.ops.pallas import autotune

    interp = jax.default_backend() != "tpu"
    if interp:
        print("# non-TPU backend: candidates run interpret mode (relative "
              "timings not meaningful — regenerate on TPU)", flush=True)
    rows = []
    for kernel, dims in SWEEP_BUCKETS.items():
        bucket = autotune.shape_bucket(*dims)
        rng = np.random.default_rng(0)
        operands = autotune._bench_operands(kernel, bucket, rng)
        best = None
        for cand in autotune.CANDIDATES[kernel]:
            dt = autotune._measure(kernel, operands, cand, "highest", interp)
            row = {
                "op": "tuning_sweep", "kernel": kernel,
                "bucket": list(bucket), **cand,
                "ms": round(dt * 1e3, 3),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
            if best is None or dt < best[1]:
                best = (cand, dt)
        print(f"# winner {kernel}: {best[0]} ({best[1] * 1e3:.3f} ms)",
              flush=True)
    return rows


def profile_tuned_vs_default():
    """Tuned-vs-default contract check: resolve each kernel family's
    geometry through a fresh sweep (``tuning="on"``, throwaway cache
    dir), then time the winner against the shipped DEFAULTS on the
    tuner's own operands.  The tuned pick must never lose — the default
    is IN the candidate grid, so a loss indicts the measurement
    harness, not the search; __main__ exits nonzero on one."""
    import tempfile

    import jax

    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.ops.pallas import autotune

    interp = jax.default_backend() != "tpu"
    if interp:
        print("# non-TPU backend: interpret-mode walls (contract still "
              "checked — both legs share the harness)", flush=True)
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        set_config(tuning="on", tuning_cache_dir=tmp)
        autotune.clear()
        try:
            for kernel, dims in SWEEP_BUCKETS.items():
                bucket = autotune.shape_bucket(*dims)
                tuned = autotune.resolve(kernel, bucket, interpret=interp)
                rng = np.random.default_rng(0)
                operands = autotune._bench_operands(kernel, bucket, rng)
                t_tuned = autotune._measure(
                    kernel, operands, tuned, "highest", interp
                )
                t_def = autotune._measure(
                    kernel, operands, autotune.DEFAULTS[kernel], "highest",
                    interp,
                )
                row = {
                    "op": "tuned_vs_default", "kernel": kernel,
                    "tuned": tuned, "default": autotune.DEFAULTS[kernel],
                    "tuned_ms": round(t_tuned * 1e3, 3),
                    "default_ms": round(t_def * 1e3, 3),
                    "speedup": round(t_def / max(t_tuned, 1e-9), 3),
                }
                rows.append(row)
                print(json.dumps(row), flush=True)
        finally:
            set_config(tuning="auto", tuning_cache_dir="")
            autotune.clear()
    return rows


def _print_progcache_stats() -> None:
    """Program-cache hit/miss report for the profiled run: the ops
    entries register every launch with utils/progcache, so after a
    shoot-out this shows how many distinct programs the sweep compiled
    and how much the repeat windows reused (the misses column is the
    compile bill a cold service would pay for these shapes)."""
    from oap_mllib_tpu.utils import progcache

    s = progcache.stats()
    print()
    print(json.dumps({"progcache": {
        k: s[k] for k in ("hits", "misses", "evictions", "hit_rate")
    }}))
    for algo, c in sorted(s["by_algo"].items()):
        print(f"# progcache {algo}: hits={c['hits']} misses={c['misses']}")
    # process-wide telemetry digest (XLA compiles, collective/stream
    # totals) — the registry view of the same sweep
    from oap_mllib_tpu import telemetry

    print()
    print(telemetry.report())


if __name__ == "__main__":
    if "--als" in sys.argv:
        rows = profile_als()
        print()
        print(markdown_als(rows))
    elif "--fused" in sys.argv:
        profile_fused()
    elif "--overlap" in sys.argv:
        profile_overlap()
    elif "--sweep" in sys.argv:
        profile_sweep()
    elif "--tuned-vs-default" in sys.argv:
        tvd = profile_tuned_vs_default()
        # re-measurement noise headroom: the sweep already took min-of-N
        # per candidate, so a real loss shows up far beyond 10%
        bad = [r for r in tvd
               if r["tuned_ms"] > r["default_ms"] * 1.10]
        if bad:
            print(f"# FAIL: tuned geometry slower than defaults: {bad}",
                  flush=True)
            _print_progcache_stats()
            sys.exit(1)
        print("# tuned geometry >= defaults on every kernel family",
              flush=True)
    else:
        rows = profile()
        print()
        print(markdown(rows))
    _print_progcache_stats()
