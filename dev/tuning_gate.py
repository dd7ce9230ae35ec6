#!/usr/bin/env python
"""CI gate: the ISSUE 17 autotuned-kernel plane must hold its contracts.

1. **Cache round-trip determinism** — mode "on" sweeps a missing
   (backend, bucket) exactly once, persists the winner under
   ``tuning_cache_dir``, and a full in-memory wipe (the fresh-process
   stand-in) re-resolves the identical geometry with ZERO new sweeps.
2. **Fresh-process zero-sweep** — a real second interpreter sharing the
   cache dir resolves from disk: ``oap_tuning_sweeps_total`` stays 0
   and the geometry matches the first process's winner bit-for-bit.
3. **Geometry parity** — the double-buffered walks are bit-identical
   across buffering depth and dispatch route at a fixed tile partition,
   and within a scaled 1e-6 across partitions (f32 reassociation only).
4. **Segmented-ring census** — ``segments >= 2`` keeps the ring-fused
   model-sharded Lloyd at exactly 3 standalone psums and within 1e-5 of
   the psum build on the 8-device virtual mesh.
5. **Tuning-off seam cost** — the per-launch ``autotune.resolve`` seam
   in the no-sweep modes ("auto" hit/default, "off") stays microseconds
   — no measurable tax on fits that never asked to tune.

Exit 1 with the offending numbers on any violation.
"""

from __future__ import annotations

import json
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
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

RING_TOL = 1e-5
PARITY_TOL = 1e-6
SEAM_BUDGET_S = 1e-3  # mean per-resolve wall, no-sweep modes


def _check(failures, ok, msg):
    if not ok:
        failures.append(msg)
        print(f"FAIL: {msg}", flush=True)


def _sweeps(kernel: str) -> float:
    from oap_mllib_tpu.telemetry import metrics as tm

    return tm.counter("oap_tuning_sweeps_total", {"kernel": kernel}).value


def cache_round_trip(failures, cache_dir: str) -> dict:
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.ops.pallas import autotune

    autotune.clear()
    set_config(tuning="on", tuning_cache_dir=cache_dir)
    before = _sweeps("kmeans")
    g1 = autotune.resolve("kmeans", (64, 64), interpret=True)
    swept = _sweeps("kmeans") - before
    _check(failures, swept == 1,
           f"first resolve ran {swept} sweeps, expected exactly 1")
    files = [f for f in os.listdir(cache_dir) if f.startswith("tune-")]
    _check(failures, len(files) == 1,
           f"cache dir holds {len(files)} entries after one sweep")

    autotune.clear()  # fresh-process stand-in: memory gone, disk stays
    before = _sweeps("kmeans")
    g2 = autotune.resolve("kmeans", (64, 64), interpret=True)
    _check(failures, _sweeps("kmeans") == before,
           "re-resolve after clear() swept again (disk entry not read)")
    _check(failures, g2 == g1,
           f"re-resolved geometry {g2} != persisted winner {g1}")
    set_config(tuning="auto", tuning_cache_dir="")
    return {"round_trip_geometry": g1}


_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from oap_mllib_tpu.config import set_config
from oap_mllib_tpu.ops.pallas import autotune
from oap_mllib_tpu.telemetry import metrics as tm

set_config(tuning="on", tuning_cache_dir=sys.argv[1])
geo = autotune.resolve("kmeans", (64, 64), interpret=True)
print(json.dumps({
    "geometry": geo,
    "sweeps": tm.counter(
        "oap_tuning_sweeps_total", {"kernel": "kmeans"}
    ).value,
}))
"""


def fresh_process_zero_sweep(failures, cache_dir: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = []
    for leg in range(2):
        p = subprocess.run(
            [sys.executable, "-c", _CHILD, cache_dir],
            capture_output=True, text=True, env=env, cwd=_REPO,
            timeout=420,
        )
        _check(failures, p.returncode == 0,
               f"subprocess leg {leg} died: {p.stderr[-1500:]}")
        if p.returncode != 0:
            return {}
        out.append(json.loads(p.stdout.strip().splitlines()[-1]))
    # the parent's round-trip leg already persisted this bucket, so BOTH
    # fresh interpreters must resolve from disk without sweeping
    _check(failures, out[0]["sweeps"] == 0 and out[1]["sweeps"] == 0,
           f"fresh processes swept ({out[0]['sweeps']}, "
           f"{out[1]['sweeps']}) times; cache not honored across exec")
    _check(failures, out[0]["geometry"] == out[1]["geometry"],
           f"fresh processes disagree: {out[0]['geometry']} vs "
           f"{out[1]['geometry']}")
    return {"fresh_process_geometry": out[0]["geometry"]}


def geometry_parity(failures) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from oap_mllib_tpu.ops.kmeans_ops import _accumulate
    from oap_mllib_tpu.ops.pallas.kmeans_kernel import lloyd_accumulate_walk
    from oap_mllib_tpu.ops.pallas.pca_kernel import pca_moments_pallas

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(700, 9)).astype(np.float32))
    w = jnp.ones((700,), jnp.float32)
    c = jnp.asarray(rng.normal(size=(5, 9)).astype(np.float32))

    # the walk at its default geometry vs the XLA accumulate: f32
    # rounding of reordered sums (counts exact)
    ref = [np.asarray(o) for o in _accumulate(x, w, c)]
    out = [np.asarray(o) for o in
           lloyd_accumulate_walk(x, w, c, interpret=True)]
    _check(failures,
           np.array_equal(out[1], ref[1])
           and np.allclose(out[0], ref[0], atol=1e-4)
           and np.allclose(out[2], ref[2], rtol=1e-5),
           "kmeans walk diverges from the XLA accumulate")

    max_dev = 0.0
    refs = {}
    for tile_rows, depth in ((256, 2), (512, 3), (1024, 2)):
        for interp in (True, False):
            got = [np.asarray(o) for o in lloyd_accumulate_walk(
                x, w, c, interpret=interp, tile_rows=tile_rows,
                depth=depth)]
            if tile_rows in refs:  # depth/route never move a bit
                _check(
                    failures,
                    all(np.array_equal(a, b)
                        for a, b in zip(got, refs[tile_rows])),
                    f"kmeans walk bits moved at fixed tile_rows="
                    f"{tile_rows} (depth={depth}, interpret={interp})",
                )
            else:
                refs[tile_rows] = got
            scale = max(1.0, float(np.abs(ref[0]).max()))
            dev = float(np.abs(got[0] - ref[0]).max()) / scale
            max_dev = max(max_dev, dev)
            _check(failures, dev <= PARITY_TOL,
                   f"kmeans walk geometry ({tile_rows},{depth},"
                   f"{interp}) dev {dev:.2e} > {PARITY_TOL}")

    xp = jnp.asarray(rng.normal(size=(900, 17)).astype(np.float32))
    mp = jnp.ones((900,), jnp.float32)
    g_ref = np.asarray(pca_moments_pallas(xp, mp, interpret=True)[0])
    scale = max(1.0, float(np.abs(g_ref).max()))
    for tile_rows, depth in ((256, 2), (1024, 3)):
        g = np.asarray(pca_moments_pallas(
            xp, mp, interpret=True, tile_rows=tile_rows, depth=depth)[0])
        dev = float(np.abs(g - g_ref).max()) / scale
        max_dev = max(max_dev, dev)
        _check(failures, dev <= PARITY_TOL,
               f"pca walk geometry ({tile_rows},{depth}) dev "
               f"{dev:.2e} > {PARITY_TOL}")
    return {"walk_parity_max_dev": max_dev}


def segmented_ring_census(failures) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.ops import kmeans_ops
    from oap_mllib_tpu.parallel.mesh import get_mesh
    from oap_mllib_tpu.telemetry import metrics as tm

    n_dev = len(jax.devices())
    _check(failures, n_dev == 8, f"gate mesh has {n_dev} devices, want 8")

    def fit(ring_segments):
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
            xs, ws, jnp.asarray(x[:5]), 29,
            jnp.asarray(1e-6, jnp.float32), m2, "data", "model",
            ring_segments=ring_segments,
        )

    set_config(model_parallel=2)
    psum_c = tm.counter("oap_collective_emitted_total", {"op": "psum"})
    p0 = psum_c.value
    c_seg = fit(ring_segments=2)
    seg_psums = psum_c.value - p0
    _check(failures, seg_psums == 3,
           f"segmented ring Lloyd emitted {seg_psums} psums, expected 3 "
           "(segmentation broke the fused epilogue)")
    set_config(ring_reduction="off")
    c_psum = fit(ring_segments=1)
    set_config(ring_reduction="auto", model_parallel=1)
    cdev = float(
        np.abs(np.asarray(c_seg[0]) - np.asarray(c_psum[0])).max()
    )
    _check(failures, cdev <= RING_TOL,
           f"segmented ring vs psum centers dev {cdev:.2e} > {RING_TOL}")
    return {"segmented_psums": int(seg_psums),
            "segmented_centers_dev": cdev}


def seam_cost(failures, cache_dir: str) -> dict:
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.ops.pallas import autotune

    out = {}
    reps = 300
    # "auto" with a warm persisted entry (the steady-state hit path),
    # "auto" with no entry (default path), and "off"
    legs = (
        ("auto_hit", "auto", cache_dir, (64, 64)),
        ("auto_default", "auto", "", (32, 8)),
        ("off", "off", "", (64, 64)),
    )
    for name, mode, cdir, bucket in legs:
        set_config(tuning=mode, tuning_cache_dir=cdir)
        autotune.resolve("kmeans", bucket, interpret=True)  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            autotune.resolve("kmeans", bucket, interpret=True)
        per = (time.perf_counter() - t0) / reps
        out[f"seam_{name}_s"] = per
        _check(failures, per <= SEAM_BUDGET_S,
               f"no-sweep resolve ({name}) costs {per * 1e6:.0f} us "
               f"per launch > {SEAM_BUDGET_S * 1e6:.0f} us budget")
    set_config(tuning="auto", tuning_cache_dir="")
    return out


def main() -> int:
    failures: list = []
    report = {}
    with tempfile.TemporaryDirectory(prefix="oap-tuning-gate-") as tmp:
        report.update(cache_round_trip(failures, tmp))
        report.update(fresh_process_zero_sweep(failures, tmp))
        report.update(geometry_parity(failures))
        report.update(segmented_ring_census(failures))
        report.update(seam_cost(failures, tmp))
    print(json.dumps({k: (round(v, 8) if isinstance(v, float) else v)
                      for k, v in report.items()}), flush=True)
    print(f"tuning gate: {'FAIL' if failures else 'OK'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
