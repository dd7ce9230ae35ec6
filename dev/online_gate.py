#!/usr/bin/env python
"""CI gate: the incremental fit paths commit cheap, exact, and safe.

Legs (ISSUE 20 acceptance):

1. **Fold-in parity + speedup** — folding a delta of brand-new users
   into a fitted ALS model matches a from-scratch refit on the same
   combined data in PREDICTION space (rel Frobenius over the folded
   rows' score vectors; the stated bound rides docs/user-guide.md —
   raw factor rows are only unique up to an invertible transform, so
   factor-space comparison would be meaningless), and costs a small
   fraction of the refit wall (>= 5x at gate scale; bench.py --online
   measures the 10k-user headline where the bound is >= 20x).
2. **Second commit is free** — a second delta in the same shape
   buckets performs ZERO new XLA compiles and ZERO autotune sweeps
   (ground truth via progcache.xla_compile_count and
   oap_tuning_sweeps_total), and a served handle answers through the
   NEW version with zero new compiles after the commit.
3. **Staleness drops across a commit** — the
   ``oap_serve_model_staleness_seconds`` gauge falls when a delta
   commits, and the handle's version bumps without eviction.
4. **Mid-commit fault leaves the old pin serving** — a fault injected
   at ``delta.solve`` on the SECOND batch of a chunked fold-in (some
   rows already solved) leaves the model table and the served answers
   bit-identical, version unchanged.
5. **Kill-mid-commit** — a REAL subprocess is SIGKILLed by the
   ``delta.solve:kill`` fault between fold-in batches: the probe
   answered before arming, the commit marker never printed (the swap
   never ran — compute-then-swap means a hard kill cannot leave a
   half-updated table behind).

Exit 1 with the offending numbers on any violation.
"""

from __future__ import annotations

import os
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

# the documented fold-in-vs-refit parity bound (docs/user-guide.md):
# relative Frobenius distance between the folded rows' prediction
# vectors and the refit's, over the same frozen candidate set
PARITY_BOUND = 0.15


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
    from oap_mllib_tpu.models.als import ALS
    from oap_mllib_tpu.models.kmeans import KMeans
    from oap_mllib_tpu.telemetry import metrics as tm
    from oap_mllib_tpu.utils import progcache
    from oap_mllib_tpu.utils.faults import FaultInjected

    rng = np.random.default_rng(20)

    # -- leg 1: fold-in parity vs refit + speedup ----------------------------
    print("== online gate: ALS fold-in parity vs from-scratch refit ==")
    nu, ni, rank = 300, 120, 6
    u = rng.integers(0, nu, size=15_000)
    i = rng.integers(0, ni, size=15_000)
    r = rng.normal(1.0, 0.5, size=15_000).astype(np.float32)
    est = dict(rank=rank, max_iter=5, reg_param=0.1, seed=3,
               num_user_blocks=1)
    base = ALS(**est).fit(u, i, r, n_users=nu, n_items=ni)
    # two deltas of brand-new users (~6 ratings each) whose padded
    # shapes land in the SAME power-of-two buckets: the first commit
    # compiles the fold-in solve, the second is the steady state the
    # gate times and compile-counts
    def _delta(lo, n):
        du = np.repeat(np.arange(lo, lo + n), 6)
        di = rng.integers(0, ni, size=du.size).astype(np.int64)
        dr = rng.normal(1.0, 0.5, size=du.size).astype(np.float32)
        return du, di, dr

    du1, di1, dr1 = _delta(nu, 700)
    du2, di2, dr2 = _delta(nu + 700, 800)
    out = base.fold_in_users(du1, di1, dr1)  # first commit: compiles
    check(out["grown"] == [nu, nu + 700],
          f"fold-in did not grow the user axis: {out['grown']}")
    compiles0 = progcache.xla_compile_count()
    sweeps0 = int(tm.family_total("oap_tuning_sweeps_total"))
    t0 = time.perf_counter()
    base.fold_in_users(du2, di2, dr2)  # steady-state commit: timed
    foldin_wall = time.perf_counter() - t0
    foldin_compiles = progcache.xla_compile_count() - compiles0
    foldin_sweeps = (
        int(tm.family_total("oap_tuning_sweeps_total")) - sweeps0
    )
    t0 = time.perf_counter()
    refit = ALS(**est).fit(
        np.concatenate([u, du1, du2]), np.concatenate([i, di1, di2]),
        np.concatenate([r, dr1, dr2]), n_users=nu + 1500, n_items=ni,
    )
    refit_wall = time.perf_counter() - t0
    pred_fold = base.user_factors_[nu:] @ base.item_factors_.T
    pred_refit = refit.user_factors_[nu:] @ refit.item_factors_.T
    rel = (np.linalg.norm(pred_fold - pred_refit)
           / np.linalg.norm(pred_refit))
    speedup = refit_wall / max(foldin_wall, 1e-9)
    print(f"  fold-in {foldin_wall * 1e3:.0f} ms vs refit "
          f"{refit_wall * 1e3:.0f} ms ({speedup:.1f}x), prediction "
          f"parity rel={rel:.3f}")
    check(rel < PARITY_BOUND,
          f"fold-in prediction parity {rel:.3f} breaches the "
          f"documented bound {PARITY_BOUND}")
    check(speedup >= 5.0,
          f"fold-in only {speedup:.1f}x faster than refit at gate "
          "scale (>= 5x required; 10k-user headline bound is 20x)")

    # -- leg 2: second delta commit is free ----------------------------------
    print("== online gate: second delta commit — zero XLA compiles, "
          "zero autotune sweeps ==")
    check(foldin_compiles == 0,
          f"second fold-in commit compiled {foldin_compiles} new XLA "
          "programs (must be 0: bucketed shapes reuse the first "
          "commit's)")
    check(foldin_sweeps == 0,
          f"second fold-in commit ran {foldin_sweeps} autotune sweeps "
          "(must be 0: tuned geometry resolves from the cache)")
    km_x = rng.normal(size=(2000, 12)).astype(np.float32)
    km = KMeans(k=5, seed=2, max_iter=4).fit(km_x)
    hk = serving.serve(km)
    probe = rng.normal(size=(64, 12)).astype(np.float32)
    hk.predict(probe)  # warm the serving bucket
    km.partial_fit(km_x[:512])  # first commit: compiles the delta pass
    compiles0 = progcache.xla_compile_count()
    sweeps0 = int(tm.family_total("oap_tuning_sweeps_total"))
    v0 = hk.model_version
    km.partial_fit(km_x[512:1024])  # same-shape delta: steady state
    served = hk.predict(probe)
    compiles = progcache.xla_compile_count() - compiles0
    sweeps = int(tm.family_total("oap_tuning_sweeps_total")) - sweeps0
    print(f"  second-commit XLA compiles: {compiles}, autotune "
          f"sweeps: {sweeps}")
    check(compiles == 0,
          f"second delta commit compiled {compiles} new XLA programs "
          "(must be 0: bucketed shapes + in-place re-pin)")
    check(sweeps == 0,
          f"second delta commit ran {sweeps} autotune sweeps "
          "(must be 0: tuned geometry resolves from the cache)")
    check(hk.model_version == v0 + 1,
          f"served handle version {hk.model_version} != {v0 + 1} "
          "after the commit")
    check(np.array_equal(served, km.predict(probe)),
          "served answers after the commit diverge from the model")

    # -- leg 3: staleness gauge drops across a commit ------------------------
    print("== online gate: staleness gauge drops across a commit ==")
    hk._committed_at -= 300.0  # age the pin five minutes
    stale_before = hk.touch_staleness()
    km.partial_fit(km_x[512:1024])
    stale_after = tm.gauge(
        "oap_serve_model_staleness_seconds", {"model": "kmeans"}
    ).value
    print(f"  staleness {stale_before:.1f}s -> {stale_after:.3f}s")
    check(stale_before > 299.0 and stale_after < 5.0,
          f"staleness did not drop across the commit "
          f"({stale_before:.1f}s -> {stale_after:.1f}s)")

    # -- leg 4: mid-commit fault leaves the old pin serving ------------------
    print("== online gate: mid-commit fault leaves the old pin "
          "serving ==")
    ha = serving.serve(base)
    ids_before = ha.recommend_for_users(np.arange(8), 5)
    table_before = np.array(base.user_factors_)
    v_before = ha.model_version
    # chunk the delta so the fault lands on the SECOND solve batch —
    # genuinely mid-commit, after rows were already solved
    set_config(fault_spec="delta.solve:err=2", online_foldin_batch=64)
    du3, di3, dr3 = _delta(50, 200)
    faulted = False
    try:
        base.fold_in_users(du3, di3, dr3)
    except FaultInjected:
        faulted = True
    set_config(fault_spec="", online_foldin_batch=0)
    check(faulted, "the armed delta.solve fault never fired")
    check(ha.model_version == v_before,
          f"version bumped across a FAILED commit "
          f"({v_before} -> {ha.model_version})")
    check(np.array_equal(base.user_factors_, table_before),
          "failed mid-commit fold-in mutated the user table")
    check(np.array_equal(ha.recommend_for_users(np.arange(8), 5),
                         ids_before),
          "served answers changed across a FAILED commit")
    print("  old pin intact: version unchanged, answers bit-identical")

    # -- leg 5: kill-mid-commit (real SIGKILL subprocess) --------------------
    print("== online gate: SIGKILL mid-commit leaves no half-updated "
          "table ==")
    _kill_mid_commit_leg()

    if failures:
        print(f"\nonline gate: {len(failures)} failure(s)")
        return 1
    print("\nonline gate: OK")
    return 0


_KILL_WORKER = r"""
import os, sys
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.models.als import ALS
rng = np.random.default_rng(9)
m = ALS(rank=3, max_iter=3, reg_param=0.1, seed=4,
        num_user_blocks=1).fit(
    rng.integers(0, 40, size=1500), rng.integers(0, 30, size=1500),
    rng.normal(1.0, 0.5, size=1500).astype(np.float32),
    n_users=40, n_items=30,
)
print("PROBE_OK", m.recommend_for_users([0, 1], 3).tolist(), flush=True)
# fire the hard kill on the SECOND solve batch: mid-commit for real
set_config(fault_spec="delta.solve:kill=2", online_foldin_batch=8)
m.fold_in_users(
    np.repeat(np.arange(10, 34), 3),
    rng.integers(0, 30, size=72), np.ones(72, np.float32),
)
print("COMMIT_OK", flush=True)
"""


def _kill_mid_commit_leg():
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c", _KILL_WORKER, repo],
        capture_output=True, text=True, timeout=240, cwd=repo, env=env,
    )
    out = p.stdout + p.stderr
    check(p.returncode == -9,
          f"worker was not SIGKILLed mid-commit (rc={p.returncode}):\n"
          f"{out[-1500:]}")
    check("PROBE_OK" in out,
          f"worker never answered the pre-kill probe:\n{out[-1500:]}")
    check("COMMIT_OK" not in out,
          "worker reached the commit marker — the kill missed the "
          "mid-commit window")
    if p.returncode == -9 and "COMMIT_OK" not in out:
        print("  worker killed between solve batches; swap never ran")


if __name__ == "__main__":
    sys.exit(main())
