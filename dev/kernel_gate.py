#!/usr/bin/env python
"""CI gate: the ISSUE 9 Pallas kernel plane must hold its contracts.

1. **Interpret-mode parity on every kernel** — the fused K-Means
   accumulate, the PCA moments/covariance kernel, the ALS batched
   normal-equation solve, and the factor Gram each reproduce their XLA
   reference (tight f32 bounds; bit-for-bit on exactly-representable
   data for the PCA pass), at every precision tier.
2. **bf16 prices ON Pallas** — the workaround retirement:
   ``precision.kernel_tier("bf16") == "default"`` and the kernel
   preference rules (``pallas_preferred`` / ``pallas_gram_preferred``)
   accept the "default" tier, so a bf16-policy fit on TPU dispatches the
   fused kernels instead of routing off them.
3. **Ring-reduction parity** — on the 8-device virtual mesh, the ring
   schedule (the exact segment rotation the TPU remote-DMA kernel
   drives) matches the psum reference at 1e-5, every rank identical;
   the <2-device fallback stays the psum path; and the ring-fused
   model-sharded Lloyd emits ZERO standalone centroid-moment psums
   (trace-time collective census) while matching the psum build.

Exit 1 with the offending numbers on any violation.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the ring legs need the suite's 8-device virtual mesh
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

RING_TOL = 1e-5


def _check(failures, ok, msg):
    if not ok:
        failures.append(msg)
        print(f"FAIL: {msg}", flush=True)


def kernel_parity(failures) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from oap_mllib_tpu.ops import als_ops
    from oap_mllib_tpu.ops.kmeans_ops import _accumulate
    from oap_mllib_tpu.ops.pallas.als_kernel import (
        factor_gram_pallas, solve_normal_eq_pallas,
    )
    from oap_mllib_tpu.ops.pallas.kmeans_kernel import lloyd_accumulate_walk
    from oap_mllib_tpu.ops.pallas.pca_kernel import covariance_pallas
    from oap_mllib_tpu.ops.pca_ops import _covariance_jit
    from oap_mllib_tpu.utils import precision as psn

    rng = np.random.default_rng(0)
    out = {}

    # K-Means fused accumulate, all tiers — well-separated blobs so the
    # fast tiers' bf16 assignment cannot flip a near-tie row between the
    # two implementations (the tier contract is "argmin is decision-only
    # on non-tied rows"); each tier is compared against the XLA path AT
    # THAT TIER, which runs the same bf16 assignment
    n, d, k = 700, 24, 9
    centers_true = rng.normal(size=(k, d)).astype(np.float32) * 20.0
    assign_true = rng.integers(0, k, n)
    x = jnp.asarray(
        centers_true[assign_true]
        + rng.normal(size=(n, d)).astype(np.float32)
    )
    w = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
    c = jnp.asarray(centers_true + rng.normal(size=(k, d)).astype(np.float32))
    # (tier, absolute bound, bound in float32 steps of the largest sum):
    # at highest two f32 programs sum in different orders, and the sums
    # lie near 1e4 here, where ONE step is 9.8e-4 — an absolute 1e-3
    # judged the data's scale, not the kernel
    f32_step = 2.0 ** -23
    for mode, atol, steps in (
        ("highest", 0.0, 4), ("high", 5e-2, 0), ("default", 2.0, 0),
    ):
        s_r, c_r, _ = _accumulate(x, w, c, precision=mode)
        s_p, c_p, _ = lloyd_accumulate_walk(
            x, w, c, mode=mode, interpret=True
        )
        dev = float(np.abs(np.asarray(s_p) - np.asarray(s_r)).max())
        atol += steps * f32_step * float(np.abs(np.asarray(s_r)).max())
        out[f"kmeans_{mode}_dev"] = dev
        _check(failures, dev <= atol,
               f"kmeans accumulate {mode}: sums dev {dev:.2e} > {atol:.2e}")
        _check(
            failures,
            float(np.abs(np.asarray(c_p) - np.asarray(c_r)).max()) <= 1e-3,
            f"kmeans accumulate {mode}: counts diverge (assignment flip)",
        )

    # PCA covariance: exact-data bit parity + general-data tiers
    half = rng.integers(-3, 4, size=(512, 17)).astype(np.float32)
    xe = jnp.asarray(np.concatenate([half, -half]))
    me = jnp.ones((1024,), jnp.float32)
    cov_p, mean_p = covariance_pallas(
        xe, me, jnp.asarray(1024.0), interpret=True
    )
    cov_r, mean_r = _covariance_jit(xe, me, jnp.asarray(1024.0))
    _check(
        failures,
        np.array_equal(np.asarray(cov_p), np.asarray(cov_r))
        and np.array_equal(np.asarray(mean_p), np.asarray(mean_r)),
        "pca covariance not bit-compatible at highest on exact data",
    )
    xg = jnp.asarray(rng.normal(size=(900, 33)).astype(np.float32) + 5.0)
    mg = jnp.asarray((rng.random(900) < 0.95).astype(np.float32))
    nv = jnp.asarray(float(np.asarray(mg).sum()))
    cg_r, _ = _covariance_jit(xg, mg, nv)
    for mode, atol in (("highest", 2e-6), ("high", 5e-5), ("default", 5e-3)):
        cg_p, _ = covariance_pallas(xg, mg, nv, mode=mode, interpret=True)
        dev = float(np.abs(np.asarray(cg_p) - np.asarray(cg_r)).max())
        out[f"pca_{mode}_dev"] = dev
        _check(failures, dev <= atol,
               f"pca covariance {mode}: dev {dev:.2e} > {atol}")

    # ALS batched solve + factor Gram
    r = 10
    m = rng.normal(size=(600, r, r)).astype(np.float32)
    a = jnp.asarray(np.einsum("nij,nkj->nik", m, m) + 0.5 * np.eye(r))
    b = jnp.asarray(rng.normal(size=(600, r)).astype(np.float32))
    n_reg = jnp.asarray(rng.integers(0, 40, 600).astype(np.float32))
    g = rng.normal(size=(64, r)).astype(np.float32)
    gram = jnp.asarray(g.T @ g * 0.01)
    ref = als_ops.regularized_solve(
        a, b, n_reg, 0.1, jnp.eye(r), gram
    )
    got = solve_normal_eq_pallas(a, b, n_reg, 0.1, gram, interpret=True)
    dev = float(np.abs(np.asarray(ref) - np.asarray(got)).max())
    out["als_solve_dev"] = dev
    _check(failures, dev <= 5e-5, f"als solve dev {dev:.2e} > 5e-5")
    zero = np.asarray(n_reg) == 0
    _check(failures, (np.asarray(got)[zero] == 0).all(),
           "als solve: empty rows not masked to zero")
    f = jnp.asarray(rng.normal(size=(777, r)).astype(np.float32))
    fg = factor_gram_pallas(f, interpret=True)
    fdev = float(np.abs(np.asarray(fg) - np.asarray(psn.pdot(f.T, f))).max())
    out["als_gram_dev"] = fdev
    _check(failures, fdev <= 2e-3, f"als factor gram dev {fdev:.2e}")
    return out


def bf16_routing(failures) -> dict:
    from oap_mllib_tpu.ops.kmeans_ops import pallas_preferred
    from oap_mllib_tpu.ops.pallas.als_kernel import pallas_solve_preferred
    from oap_mllib_tpu.ops.pallas.pca_kernel import pallas_gram_preferred
    from oap_mllib_tpu.utils import precision as psn

    tier = psn.kernel_tier("bf16", "highest")
    _check(failures, tier == "default",
           f"kernel_tier('bf16') -> {tier!r}, expected 'default'")
    _check(failures, pallas_preferred(256, 1000, tier),
           "bf16 tier routes OFF the K-Means Pallas kernel "
           "(workaround not retired)")
    _check(failures, pallas_gram_preferred(256, tier),
           "bf16 tier routes OFF the PCA Pallas kernel")
    _check(failures, pallas_solve_preferred(10),
           "default rank routes OFF the ALS Pallas solve")
    return {"bf16_kernel_tier": tier}


def ring_parity(failures) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.ops import kmeans_ops
    from oap_mllib_tpu.ops.pallas.ring_reduce import ring_allreduce
    from oap_mllib_tpu.parallel import collective
    from oap_mllib_tpu.parallel.mesh import get_mesh
    from oap_mllib_tpu.telemetry import metrics as tm
    from oap_mllib_tpu.utils.jax_compat import shard_map

    rng = np.random.default_rng(1)
    n_dev = len(jax.devices())
    _check(failures, n_dev == 8, f"gate mesh has {n_dev} devices, want 8")
    mesh = jax.make_mesh((n_dev,), ("data",))
    g = rng.normal(size=(n_dev, 64, 96)).astype(np.float32) * 10.0
    gd = jax.device_put(
        jnp.asarray(g), NamedSharding(mesh, P("data", None, None))
    )

    def prog(fn):
        return jax.jit(
            shard_map(
                lambda b: fn(b[0])[None], mesh=mesh,
                in_specs=P("data", None, None),
                out_specs=P("data", None, None), check_vma=False,
            )
        )

    ring = np.asarray(
        prog(lambda v: ring_allreduce(v, "data", n_dev))(gd)
    )
    ref = np.asarray(prog(lambda v: collective.psum(v, "data"))(gd))
    scale = float(np.abs(ref[0]).max())
    dev = float(np.abs(ring[0] - ref[0]).max()) / scale
    rank_identical = all(
        np.array_equal(ring[0], ring[i]) for i in range(n_dev)
    )
    _check(failures, dev <= RING_TOL,
           f"ring vs psum relative dev {dev:.2e} > {RING_TOL}")
    _check(failures, rank_identical, "ring results differ across ranks")

    # ring-fused model-sharded Lloyd: census + parity vs the psum build
    def fit(max_iter):
        data_rng = np.random.default_rng(7)
        x = data_rng.normal(size=(512, 16)).astype(np.float32)
        m2 = get_mesh()
        xs = jax.device_put(
            jnp.asarray(x), NamedSharding(m2, P("data", "model"))
        )
        ws = jax.device_put(
            jnp.ones((512,), jnp.float32), NamedSharding(m2, P("data"))
        )
        return kmeans_ops.lloyd_run_model_sharded(
            xs, ws, jnp.asarray(x[:5]), max_iter,
            jnp.asarray(1e-6, jnp.float32), m2, "data", "model",
        )

    set_config(model_parallel=2)
    psum_c = tm.counter("oap_collective_emitted_total", {"op": "psum"})
    p0 = psum_c.value
    c_ring = fit(31)
    ring_psums = psum_c.value - p0
    # score (loop) + d2 (final) + move — ZERO centroid-moment psums
    _check(failures, ring_psums == 3,
           f"ring Lloyd build emitted {ring_psums} psums, expected 3 "
           "(standalone centroid allreduces not eliminated)")
    set_config(ring_reduction="off")
    c_psum = fit(31)
    cdev = float(
        np.abs(np.asarray(c_ring[0]) - np.asarray(c_psum[0])).max()
    )
    _check(failures, cdev <= RING_TOL,
           f"ring vs psum Lloyd centers dev {cdev:.2e} > {RING_TOL}")
    set_config(ring_reduction="auto", model_parallel=1)
    # <2-device fallback: a 1-device mesh must resolve to the psum path
    mesh1 = get_mesh(n_devices=1)
    _check(failures, not kmeans_ops.ring_enabled(mesh1, "data"),
           "ring_enabled True on a 1-device reduce axis")
    return {
        "ring_rel_dev": dev,
        "ring_lloyd_psums": int(ring_psums),
        "ring_lloyd_centers_dev": cdev,
    }


def main() -> int:
    failures: list = []
    report = {}
    report.update(kernel_parity(failures))
    report.update(bf16_routing(failures))
    report.update(ring_parity(failures))
    print(json.dumps({k: (round(v, 8) if isinstance(v, float) else v)
                      for k, v in report.items()}), flush=True)
    print(f"kernel gate: {'FAIL' if failures else 'OK'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
