#!/usr/bin/env python
"""Bring-up smoke: K-Means, PCA and ALS end to end on the chip.

    python chip_smoke.py                # one TPU chip: fit + serve
    python chip_smoke.py --chips 4      # one host, four chips: sharded fits
    python chip_smoke.py --rehearse     # tiny sizes, any backend, ends ok:false

One process drives the chip through the entry points a user calls —
``KMeans.fit`` / ``PCA.fit`` / ``ALS.fit``, ``serving.serve`` — pinned
to ``device="tpu", fallback=False`` so no rung of the resilience ladder
and no dispatch rule can carry a fit to NumPy unseen.  Data are made
from ``--seed``.  Every phase prints its wall, the XLA compile seconds
and count it paid, and the peak device bytes; any failed check is a
non-zero exit.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

Shapes (one chip): K-Means 1,048,576 x 256 f32, k=1000, 10 iterations;
PCA 1,048,576 x 128, 10 components; implicit ALS at MovieLens-1M scale
(6040 x 3706, 1M ratings, rank 10, alpha 40).

What each fit is held to, independently of ``ops/``:

- K-Means: on a fixed 131,072-row subsample, the device's cost of the
  returned centres equals a float64 NumPy recomputation to 1e-4
  relative, and lies within 5% of the cost NumPy Lloyd
  (``fallback/kmeans_np.py``) reaches on that subsample from the same
  initial centres in the same number of iterations (the full-table fit
  cannot overfit the subsample, so it sits a little above).
- PCA: explained-variance ratios within 1e-4 absolute and component
  |cos| >= 1 - 1e-4 against ``np.linalg.eigh`` of the float64 covariance.
- ALS: predictions on the observed pairs within 2% relative RMS of
  ``fallback/als_np.py`` run from the same seed (float64 solves against
  the chip's float32 Cholesky, ten alternations), and the same train-set
  RMSE against preference 1 to 0.01.
- Serving: every served answer equals the direct model call bit for bit.

``--chips 4`` runs the sharded fits only — data-parallel K-Means, the
same with ``model_parallel=2`` (the route that selects the remote-DMA
ring), the model-sharded-Gram PCA, block ALS with
``als_item_layout="sharded"`` — each against the same fit on a
one-device mesh, and checks that the row tables really span four
devices.  Parity bounds: PCA and ALS at the 8-device CPU tests' own
(components 1e-3, variance ratios 1e-4, factors 2e-4).  K-Means cannot
keep theirs (centres 1e-5 at 512 rows) at a million: a row on a
near-tie may change sides when the f32 sums are reordered, which moves
one centre by |x - c| / cluster size and the objective not at all — so
the cost must agree to 1e-5 relative, every row must be counted once,
at most 1 row in 1000 may change sides (ten iterations from a random
start split a quarter of the blobs between two centres, whose border
runs through dense data: 3 in 10,000 were seen), and the median centre
must agree to 1e-4.  No bound is put on the worst centre: what a moved
row does to a small cluster is not an error (2.6e-2 was seen).
"""

import argparse
import contextlib
import faulthandler
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

WATCHDOG_S = 1150

SIZES = {
    "full": dict(
        km_n=1 << 20, km_d=256, km_k=1000, km_iters=10, sub=1 << 17,
        pca_n=1 << 20, pca_d=128, pca_k=10,
        als_users=6040, als_items=3706, als_nnz=1_000_000, als_rank=10,
        als_iters=10, warm_rows=4096, mc_k=256, mc_als_iters=3,
    ),
    "tiny": dict(
        km_n=4096, km_d=32, km_k=16, km_iters=5, sub=1024,
        pca_n=4096, pca_d=16, pca_k=4,
        als_users=240, als_items=160, als_nnz=6000, als_rank=4,
        als_iters=4, warm_rows=256, mc_k=8, mc_als_iters=3,
    ),
}


class CheckFailed(AssertionError):
    pass


class Smoke:
    """Run state: the device, the size table, and the phase bookkeeping."""

    def __init__(self, args):
        import jax

        self.args = args
        self.jax = jax
        dev = jax.devices()[0]
        self.device = {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        }
        self.on_chip = dev.platform == "tpu"
        self.sz = SIZES["tiny" if args.rehearse else "full"]
        self.rng = np.random.default_rng(args.seed)

    # -- checks ---------------------------------------------------------------
    def check(self, what: str, ok: bool, detail: str = "") -> None:
        print(f"  check {what}: {'ok' if ok else 'FAILED'} {detail}".rstrip(),
              flush=True)
        if not ok:
            raise CheckFailed(f"{what} {detail}")

    def chip_check(self, what: str, ok: bool, detail: str = "") -> None:
        """A check only the chip can pass (kernel choice, HBM, sharding
        over real devices): a rehearsal on another backend names it and
        goes on — its last line says ok:false whatever happens."""
        if self.on_chip:
            self.check(what, ok, detail)
        else:
            print(f"  check {what}: not checked on "
                  f"{self.device['platform']} {detail}".rstrip(), flush=True)

    # -- phases ---------------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name: str):
        from oap_mllib_tpu.utils import progcache

        print(f"== {name}", flush=True)
        c0 = progcache.xla_compile_count()
        s0 = progcache.xla_compile_secs()
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in self.jax.local_devices()
        ]
        print(
            f"   {name}: wall {wall:.2f}s, xla compiles "
            f"{progcache.xla_compile_count() - c0} in "
            f"{progcache.xla_compile_secs() - s0:.2f}s, peak device bytes "
            f"{peaks if len(peaks) > 1 else peaks[0]}",
            flush=True,
        )

    def fit_checks(self, label: str, summary, kernel_key, want_kernel):
        """The assertions every accelerated fit shares."""
        get = (
            summary.get if isinstance(summary, dict)
            else lambda k, d=None: getattr(summary, k, d)
        )
        self.check(f"{label} accelerated", bool(get("accelerated")))
        res = get("resilience") or {}
        self.check(
            f"{label} no degradations/retries/faults",
            res.get("degradations") == 0 and res.get("retries") == 0
            and res.get("faults") == 0,
            f"(resilience={ {k: res.get(k) for k in ('degradations', 'retries', 'faults', 'ladder')} })",
        )
        # the Pallas kernels are dispatched on a TPU only
        (self.chip_check if want_kernel == "pallas" else self.check)(
            f"{label} kernel", get(kernel_key) == want_kernel,
            f"(recorded {get(kernel_key)!r}, dispatch promises "
            f"{want_kernel!r})",
        )


def build_native(sm: Smoke) -> None:
    """``native/build/`` is git-ignored: build the library from the
    committed sources, then load it (the loader otherwise degrades to
    NumPy with an info log)."""
    with sm.phase("native library"):
        proc = subprocess.run(
            ["make", "-B", "-C", os.path.join(HERE, "oap_mllib_tpu", "native")],
            capture_output=True, text=True,
        )
        sm.check("make -B native", proc.returncode == 0,
                 proc.stderr.strip()[-400:])
        from oap_mllib_tpu import native

        sm.check("native library loaded", native.available())


def _blobs(rng, n, d, k, spread=0.3):
    proto = rng.standard_normal((k, d), dtype=np.float32)
    x = rng.standard_normal((n, d), dtype=np.float32)
    x *= spread
    x += proto[rng.integers(k, size=n)]
    return x


def _decaying(rng, n, d):
    """A decaying spectrum on a random basis, off-centre: the top
    eigenvectors are well separated, the mean is not negligible."""
    basis, _ = np.linalg.qr(rng.standard_normal((d, d)))
    scales = 1.5 ** -np.arange(d, dtype=np.float64) * 4 + 0.05
    x = rng.standard_normal((n, d), dtype=np.float32)
    x = (x * scales.astype(np.float32)) @ basis.T.astype(np.float32)
    x += rng.standard_normal(d, dtype=np.float32) * 3
    return x


def phase_kmeans(sm: Smoke):
    from oap_mllib_tpu import KMeans
    from oap_mllib_tpu.fallback.kmeans_np import lloyd_np

    sz = sm.sz
    n, d, k, iters = sz["km_n"], sz["km_d"], sz["km_k"], sz["km_iters"]
    with sm.phase(f"kmeans fit {n}x{d} k={k} max_iter={iters}"):
        x = _blobs(sm.rng, n, d, k)
        seed = sm.args.seed
        # max_iter=0 returns the k-means|| initial centres of this seed:
        # the start the NumPy reference Lloyd needs
        init = KMeans(k=k, max_iter=0, seed=seed).fit(x).cluster_centers_
        t0 = time.perf_counter()
        model = KMeans(k=k, max_iter=iters, seed=seed).fit(x)
        print(f"   KMeans.fit wall {time.perf_counter() - t0:.2f}s, "
              f"{model.summary.num_iter} iterations, cost "
              f"{model.summary.training_cost:.6g}", flush=True)
        sm.fit_checks("kmeans", model.summary, "kernel", "pallas")
        c = model.cluster_centers_
        sm.check("kmeans centres finite, shape",
                 c.shape == (k, d) and bool(np.all(np.isfinite(c))),
                 f"{c.shape}")
    with sm.phase("kmeans reference (NumPy, 128k-row subsample)"):
        sub = x[:: max(1, n // sz["sub"])][: sz["sub"]].astype(np.float64)
        c64 = c.astype(np.float64)
        d2 = (
            (sub * sub).sum(1)[:, None] + (c64 * c64).sum(1)[None, :]
            - 2.0 * sub @ c64.T
        )
        cost_np = float(np.maximum(d2, 0.0).min(1).sum())
        cost_dev = model.compute_cost(sub.astype(np.float32))
        rel = abs(cost_dev - cost_np) / cost_np
        sm.check("kmeans device cost == float64 NumPy cost", rel <= 1e-4,
                 f"(device {cost_dev:.6g}, numpy {cost_np:.6g}, rel {rel:.2e})")
        _, ref_iters, cost_ref = lloyd_np(
            sub, init.astype(np.float64), model.summary.num_iter, 1e-4
        )
        ratio = cost_np / cost_ref
        sm.check(
            "kmeans cost within 5% of NumPy Lloyd from the same init",
            0.95 <= ratio <= 1.05,
            f"(fit {cost_np:.6g}, reference {cost_ref:.6g} after "
            f"{ref_iters} iterations, ratio {ratio:.4f})",
        )
    return model, x


def phase_pca(sm: Smoke):
    from oap_mllib_tpu import PCA

    sz = sm.sz
    n, d, k = sz["pca_n"], sz["pca_d"], sz["pca_k"]
    with sm.phase(f"pca fit {n}x{d} k={k}"):
        x = _decaying(sm.rng, n, d)
        t0 = time.perf_counter()
        model = PCA(k=k).fit(x)
        print(f"   PCA.fit wall {time.perf_counter() - t0:.2f}s", flush=True)
        sm.fit_checks("pca", model.summary, "kernel", "pallas")
    with sm.phase("pca reference (np.linalg.eigh, float64 covariance)"):
        x64 = x.astype(np.float64)
        x64 -= x64.mean(axis=0)
        vals, vecs = np.linalg.eigh(x64.T @ x64 / (n - 1))
        vals, vecs = vals[::-1], vecs[:, ::-1]
        ratio_ref = vals[:k] / vals.sum()
        dv = float(np.max(np.abs(model.explained_variance_ - ratio_ref)))
        sm.check("pca explained-variance ratios", dv <= 1e-4,
                 f"(max abs dev {dv:.2e})")
        cos = np.abs(np.sum(model.components_ * vecs[:, :k], axis=0))
        sm.check("pca components |cos| vs eigh", float(cos.min()) >= 1 - 1e-4,
                 f"(min |cos| {cos.min():.8f})")
    return model


def _ratings(sm: Smoke):
    """Implicit-feedback triples at the size table's scale: popularity
    follows a mild power law, strengths a clipped low-rank model."""
    sz, rng = sm.sz, sm.rng
    nu, ni, nnz = sz["als_users"], sz["als_items"], sz["als_nnz"]
    pop = 1.0 / np.arange(1, ni + 1) ** 0.6
    users = rng.integers(nu, size=nnz)
    items = rng.choice(ni, size=nnz, p=pop / pop.sum())
    fu = rng.standard_normal((nu, 6), dtype=np.float32)
    fi = rng.standard_normal((ni, 6), dtype=np.float32)
    r = 3.0 + np.einsum("ij,ij->i", fu[users], fi[items]) * 0.6
    return users, items, np.clip(r, 1.0, 5.0).astype(np.float32), nu, ni


def phase_als(sm: Smoke):
    from oap_mllib_tpu import ALS
    from oap_mllib_tpu.fallback import als_np

    sz = sm.sz
    rank, iters = sz["als_rank"], sz["als_iters"]
    users, items, ratings, nu, ni = _ratings(sm)
    with sm.phase(f"als fit {nu}x{ni} nnz={len(users)} rank={rank} "
                  f"max_iter={iters}"):
        t0 = time.perf_counter()
        model = ALS(
            rank=rank, max_iter=iters, reg_param=0.1, implicit_prefs=True,
            alpha=40.0, seed=sm.args.seed,
        ).fit(users, items, ratings, n_users=nu, n_items=ni)
        print(f"   ALS.fit wall {time.perf_counter() - t0:.2f}s", flush=True)
        sm.fit_checks("als", model.summary, "als_kernel", "grouped")
        sm.check(
            "als factors finite",
            bool(np.all(np.isfinite(model.user_factors_))
                 and np.all(np.isfinite(model.item_factors_))),
        )
    with sm.phase("als reference (fallback/als_np.py, same seed)"):
        x_ref, y_ref = als_np.als_np(
            users, items, ratings, nu, ni, rank, max_iter=iters, reg=0.1,
            alpha=40.0, implicit=True, seed=sm.args.seed,
        )
        p_ref = np.einsum("ij,ij->i", x_ref[users], y_ref[items])
        p_dev = model.predict(users, items)
        rel = float(np.sqrt(np.mean((p_dev - p_ref) ** 2))
                    / np.sqrt(np.mean(p_ref ** 2)))
        sm.check("als predictions on the observed pairs", rel <= 0.02,
                 f"(rel RMS dev {rel:.2e})")
        rmse_dev = float(np.sqrt(np.mean((1.0 - p_dev) ** 2)))
        rmse_ref = float(np.sqrt(np.mean((1.0 - p_ref) ** 2)))
        sm.check("als train-set RMSE vs preference 1",
                 abs(rmse_dev - rmse_ref) <= 0.01,
                 f"(device {rmse_dev:.4f}, reference {rmse_ref:.4f})")
    return model, users, items


def phase_serving(sm: Smoke, km, x, als, users, items):
    from oap_mllib_tpu import serving
    from oap_mllib_tpu.utils import progcache

    warm = sm.sz["warm_rows"]
    with sm.phase("serving: serve + warmup"):
        hk = serving.serve(km)
        ha = serving.serve(als)
        print(f"   warmed {hk.warmup(warm)} kmeans and "
              f"{ha.warmup(warm)} als bucket programs", flush=True)
    with sm.phase("serving: requests"):
        c0 = progcache.xla_compile_count()
        sizes = [1, 7, 100, warm // 4 + 3, warm]
        for s in sizes:
            served = hk.predict(x[:s])
            sm.check(f"served kmeans predict({s}) == model.predict",
                     np.array_equal(served, km.predict(x[:s])))
        # the assignment itself, against float64 NumPy on one batch: a
        # served centre that is not the float64 nearest may be farther
        # only by what f32 rounding of |x|^2 + |c|^2 - 2 x.c can explain:
        # 8 eps of the largest |x|^2 + |c|^2.  (On the chip a distance was
        # off by up to 1.4e-4 at sums near 600, i.e. 2 eps; two distances
        # make 4, and the bound doubles that.  bfloat16 would be 2^-8 of
        # the distance itself, some 25 times this bound.)
        xb = x[:warm].astype(np.float64)
        c64 = km.cluster_centers_.astype(np.float64)
        x_sq, c_sq = (xb * xb).sum(1), (c64 * c64).sum(1)
        d2 = x_sq[:, None] + c_sq[None, :] - 2.0 * xb @ c64.T
        best = d2.min(axis=1)
        got = d2[np.arange(warm), hk.predict(x[:warm])]
        bound = 8 * float(np.finfo(np.float32).eps) * (x_sq.max() + c_sq.max())
        excess = float(np.max(got - best))
        sm.check("served kmeans ids vs float64 argmin", excess <= bound,
                 f"({float(np.mean(got == best)):.5f} of the ids equal, "
                 f"worst d2 excess {excess:.2e}, f32 bound {bound:.2e})")
        for s in (1, 10, min(256, warm)):
            uid = users[:s]
            ids, scores = ha.recommend_for_users(uid, 10, with_scores=True)
            ids_m, scores_m = als.recommend_for_users(
                uid, 10, with_scores=True
            )
            sm.check(
                f"served als recommend_for_users({s}) == model",
                np.array_equal(ids, ids_m)
                and np.array_equal(scores, scores_m),
            )
        full = als.user_factors_[users[:64]].astype(np.float64) @ \
            als.item_factors_.astype(np.float64).T
        top = -np.sort(-full, axis=1)[:, :10]
        _, sc = ha.recommend_for_users(users[:64], 10, with_scores=True)
        dev = float(np.max(np.abs(sc - top)))
        sm.check("served als top-10 scores vs float64", dev <= 1e-4,
                 f"(max abs dev {dev:.2e})")
        sm.check(
            "served als predict == model.predict",
            np.array_equal(ha.predict(users[:100], items[:100]),
                           als.predict(users[:100], items[:100])),
        )
        print(f"   xla compiles during requests: "
              f"{progcache.xla_compile_count() - c0}", flush=True)


# -- four chips ---------------------------------------------------------------


@contextlib.contextmanager
def one_device_mesh():
    """Fit on ``get_mesh(n_devices=1)`` while four devices are visible:
    the estimators size their mesh from ``jax.devices()``, so the
    comparison fit steers them here, not through an option of theirs."""
    from oap_mllib_tpu.models import kmeans as km_mod, pca as pca_mod
    from oap_mllib_tpu.parallel.mesh import get_mesh

    def mesh1(*_a, **_k):
        return get_mesh(n_devices=1, model_parallel=1)

    saved = km_mod.get_mesh, pca_mod.get_mesh
    km_mod.get_mesh = pca_mod.get_mesh = mesh1
    try:
        yield
    finally:
        km_mod.get_mesh, pca_mod.get_mesh = saved


@contextlib.contextmanager
def record_tables(out: list):
    """Keep the row tables a fit builds (``DenseTable.from_numpy``)."""
    from oap_mllib_tpu.data.table import DenseTable

    orig = DenseTable.from_numpy.__func__

    def recording(cls, *a, **k):
        t = orig(cls, *a, **k)
        out.append(t.data)
        return t

    DenseTable.from_numpy = classmethod(recording)
    try:
        yield
    finally:
        DenseTable.from_numpy = classmethod(orig)


def spans_devices(sm: Smoke, what: str, arr, n_dev: int) -> None:
    per = [int(s.data.nbytes) for s in arr.addressable_shards]
    sm.check(
        f"{what} spans {n_dev} devices, balanced",
        len(arr.sharding.device_set) == n_dev and len(per) == n_dev
        and max(per) <= 2 * max(min(per), 1),
        f"(devices {len(arr.sharding.device_set)}, per-device bytes {per})",
    )


def phase_multichip(sm: Smoke):
    from oap_mllib_tpu import ALS, KMeans, PCA
    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.fallback import als_np

    sz = sm.sz
    n_dev = sm.device["count"]
    seed = sm.args.seed
    n, d, k, iters = sz["km_n"], sz["km_d"], sz["mc_k"], sz["km_iters"]

    def km_fit():
        return KMeans(k=k, max_iter=iters, seed=seed,
                      init_mode="random").fit(x)

    def km_parity(label, m, ref):
        """Another device count reorders f32 sums, and a row that sits on
        a near-tie between two centres may then change sides: the
        objective does not feel it, one small cluster's centre does
        (by |x - c| / cluster size).  So the fit is held to the
        objective tightly, to every row being counted once, to the
        number of rows that changed sides, and to the centres in bulk;
        the worst centre is printed, not bounded."""
        sm.check(f"{label} iterations == one-device fit",
                 m.summary.num_iter == ref.summary.num_iter,
                 f"({m.summary.num_iter} vs {ref.summary.num_iter})")
        rel = abs(m.summary.training_cost - ref.summary.training_cost) \
            / ref.summary.training_cost
        sm.check(f"{label} cost vs one-device fit", rel <= 1e-5,
                 f"(rel {rel:.2e})")
        sizes = m.summary.cluster_sizes.astype(np.float64)
        sm.check(f"{label} cluster sizes sum to the table",
                 float(sizes.sum()) == n_rows, f"({sizes.sum():.0f})")
        moved = float(np.abs(sizes - ref.summary.cluster_sizes).sum()) / 2
        sm.check(f"{label} rows that changed sides <= 1 in 1000",
                 moved <= 1e-3 * n_rows, f"({moved:.0f} of {n_rows})")
        dev = np.max(np.abs(m.cluster_centers_ - ref.cluster_centers_),
                     axis=1)
        sm.check(f"{label} centres vs one-device fit: median within 1e-4",
                 float(np.median(dev)) <= 1e-4,
                 f"(median {np.median(dev):.2e}, max {dev.max():.2e}, "
                 f"{float(np.mean(dev <= 1e-4)):.1%} within 1e-4)")

    n_rows = n
    x = _blobs(sm.rng, n, d, k)
    with sm.phase(f"kmeans {n}x{d} k={k} on a one-device mesh"):
        with one_device_mesh():
            km_ref = km_fit()
        sm.fit_checks("kmeans[1]", km_ref.summary, "kernel", "pallas")
    with sm.phase(f"kmeans data-parallel over {n_dev} devices"):
        tables = []
        with record_tables(tables):
            km_dp = km_fit()
        # every chip walks its own shard with the one-chip kernel
        sm.fit_checks("kmeans[dp]", km_dp.summary, "kernel", "pallas")
        spans_devices(sm, "kmeans[dp] row table", tables[0], n_dev)
        km_parity("kmeans[dp]", km_dp, km_ref)

    n, d, kc = sz["pca_n"], sz["pca_d"], sz["pca_k"]
    xp = _decaying(sm.rng, n, d)
    with sm.phase(f"pca {n}x{d} on a one-device mesh"):
        with one_device_mesh():
            pca_ref = PCA(k=kc).fit(xp)
    with sm.phase("pca model_parallel=2 (model-sharded Gram)"):
        set_config(model_parallel=2)
        tables = []
        with record_tables(tables):
            pca_mp = PCA(k=kc).fit(xp)
        set_config(model_parallel=1)
        sm.fit_checks("pca[mp]", pca_mp.summary, "kernel", "model_sharded")
        sm.check("pca[mp] mesh", pca_mp.summary["mesh_shape"]
                 == {"data": n_dev // 2, "model": 2},
                 f"({pca_mp.summary['mesh_shape']})")
        spans_devices(sm, "pca[mp] row table", tables[0], n_dev)
        dc = float(np.max(np.abs(
            np.abs(pca_mp.components_) - np.abs(pca_ref.components_))))
        sm.check("pca[mp] |components| vs one-device fit", dc <= 1e-3,
                 f"(max abs dev {dc:.2e})")
        dv = float(np.max(np.abs(
            pca_mp.explained_variance_ - pca_ref.explained_variance_)))
        sm.check("pca[mp] variance ratios vs one-device fit", dv <= 1e-4,
                 f"(max abs dev {dv:.2e})")
    del xp

    users, items, ratings, nu, ni = _ratings(sm)
    rank, it = sz["als_rank"], sz["mc_als_iters"]
    init = (als_np.init_factors(nu, rank, seed),
            als_np.init_factors(ni, rank, seed + 1))
    kw = dict(rank=rank, max_iter=it, reg_param=0.1, implicit_prefs=True,
              alpha=40.0, seed=seed)
    with sm.phase(f"als {nu}x{ni} nnz={len(users)} on one device"):
        als_ref = ALS(num_user_blocks=1, **kw).fit(
            users, items, ratings, n_users=nu, n_items=ni, init=init)
        sm.fit_checks("als[1]", als_ref.summary, "als_kernel", "grouped")
    with sm.phase(f"block als over {n_dev} devices, item layout sharded"):
        set_config(als_item_layout="sharded")
        als_sh = ALS(**kw).fit(
            users, items, ratings, n_users=nu, n_items=ni, init=init)
        set_config(als_item_layout="auto")
        s = als_sh.summary
        sm.check("als[sharded] accelerated, layout",
                 bool(s["accelerated"]) and s["item_layout"] == "sharded"
                 and s["num_user_blocks"] == n_dev,
                 f"(item_layout {s['item_layout']}, blocks "
                 f"{s['num_user_blocks']}, kernel {s.get('als_kernel')})")
        res = s["resilience"]
        sm.check("als[sharded] no degradations/retries/faults",
                 res["degradations"] == 0 and res["retries"] == 0
                 and res["faults"] == 0, f"({res})")
        spans_devices(sm, "als[sharded] user blocks",
                      als_sh._sharded_user[0], n_dev)
        spans_devices(sm, "als[sharded] item blocks",
                      als_sh._sharded_item[0], n_dev)
        for name, a, b in (
            ("user", als_sh.user_factors_, als_ref.user_factors_),
            ("item", als_sh.item_factors_, als_ref.item_factors_),
        ):
            sm.check(f"als[sharded] {name} factors vs one-device fit",
                     bool(np.allclose(a, b, atol=2e-4, rtol=2e-4)),
                     f"(max abs dev {float(np.max(np.abs(a - b))):.2e})")
    # last, so that a wedged remote-DMA ring (its first meeting with real
    # chips) cannot take the other comparisons with it
    with sm.phase("kmeans model_parallel=2 (ring-reduced moments)"):
        set_config(model_parallel=2)
        tables = []
        with record_tables(tables):
            km_mp = km_fit()
        set_config(model_parallel=1)
        sm.fit_checks("kmeans[mp]", km_mp.summary, "kernel", "model_sharded")
        spans_devices(sm, "kmeans[mp] row table", tables[0], n_dev)
        km_parity("kmeans[mp]", km_mp, km_ref)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fit + serve on one chip (default); 4: the "
                         "sharded fits on one four-chip host, nothing else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated table")
    ap.add_argument("--rehearse", action="store_true",
                    help="same phases and checks at tiny sizes on whatever "
                         "backend is there; always ends ok:false, exit 1")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(HERE, "oap_mllib_tpu")):
        print("chip_smoke: the oap_mllib_tpu package is not next to this "
              "script", file=sys.stderr)
        return 3

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU — JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly "
              f"{args.chips} device(s), JAX found {len(jax.devices())}",
              file=sys.stderr)
        return 2

    # the contract is an exit inside 1200 s whatever happens: a wedged
    # collective cannot be interrupted from Python, so a watchdog thread
    # dumps the stacks and ends the process
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    from oap_mllib_tpu.config import set_config
    from oap_mllib_tpu.utils import membudget, progcache

    sm = Smoke(args)
    # nothing on this path may hide the device: the TPU or nothing, no
    # NumPy rung, no sanitizer perturbing the programs
    set_config(device="tpu" if sm.on_chip else "auto", fallback=False,
               sanitizers="", seed=args.seed)
    cache_dir = progcache.use_checkout_cache(os.path.join(HERE, ".jax_cache"))
    print(f"device: {json.dumps(sm.device)}; mode: "
          f"{'rehearsal' if args.rehearse else 'full'}; compile cache: "
          f"{cache_dir}", flush=True)
    hbm = membudget.detect_hbm_bytes()
    sm.chip_check("detected HBM bytes non-zero", hbm > 0, f"({hbm})")

    build_native(sm)
    if args.chips == 4:
        phase_multichip(sm)
    else:
        km, x = phase_kmeans(sm)
        phase_pca(sm)
        als, users, items = phase_als(sm)
        phase_serving(sm, km, x, als, users, items)

    ok = sm.on_chip and not args.rehearse
    line = {"ok": ok, "device": sm.device}
    if not ok:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
