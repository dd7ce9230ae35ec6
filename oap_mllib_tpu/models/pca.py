"""PCA estimator with Spark-MLlib-compatible parameters.

API parity target: ``org.apache.spark.ml.feature.PCA`` as shimmed by the
reference (spark-3.1.1/ml/feature/PCA.scala): param k; model surface
``pc`` (d x k principal-component matrix), ``explainedVariance`` (top-k
variance ratios), transform = projection WITHOUT mean-centering.

Dispatch mirrors the reference guard (PCA.scala:103): accelerated iff
platform compatible AND numFeatures < 65535.  Explained-variance ratios are
normalized by total variance, per Spark's
computePrincipalComponentsAndExplainedVariance (the oracle used by the
reference's own parity suite, IntelPCASuite.scala:51-54).
"""

from __future__ import annotations

import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from oap_mllib_tpu.config import get_config
from oap_mllib_tpu import telemetry
from oap_mllib_tpu.data.table import DenseTable
from oap_mllib_tpu.fallback.pca_np import pca_np
from oap_mllib_tpu.ops import pca_ops
from oap_mllib_tpu.ops.pallas import autotune
from oap_mllib_tpu.ops.pallas.pca_kernel import MXU_PASSES
from oap_mllib_tpu.parallel.mesh import get_mesh
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import checkpoint as ckpt_mod
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.dispatch import MAX_PCA_FEATURES, should_accelerate
from oap_mllib_tpu.utils.timing import Timings, phase_timer


class PCAModel:
    def __init__(self, components: np.ndarray, explained_variance: np.ndarray,
                 summary: Optional[dict] = None):
        # components: (d, k), columns are principal axes (Spark's `pc`)
        self.components_ = np.asarray(components)
        self.explained_variance_ = np.asarray(explained_variance)
        self.summary = summary or {}
        # device-copy cache (serving/registry.pin): transform never
        # re-uploads the components; a refit re-stages exactly once
        self._dev_cache: dict = {}

    @property
    def k(self) -> int:
        return self.components_.shape[1]

    def transform(self, x) -> np.ndarray:
        """Project into the PC basis (no centering — Spark parity).
        Accepts a ChunkSource for out-of-core scoring (the (n, k)
        projection is the caller's host memory).  Every path routes
        through the bucketed serving program (serving/batcher.py)
        against the PINNED components — no per-call re-upload, bounded
        compiled-shape count under jittered batch sizes."""
        from oap_mllib_tpu.data.stream import ChunkSource
        from oap_mllib_tpu.serving import batcher
        from oap_mllib_tpu.serving.registry import pin

        if isinstance(x, ChunkSource):
            parts = [self.transform(c[:v]) for c, v in x]
            if not parts:  # empty source: same contract as an empty array
                return self.transform(np.zeros((0, x.n_features)))
            return np.concatenate(parts)
        x = np.asarray(x, dtype=self.components_.dtype)
        comp = pin(self._dev_cache, "components", self.components_)
        return batcher.project_pca(comp, x)

    def save(self, path: str) -> None:
        """Atomic per-file writes, metadata last (data/io primitives) —
        the KMeansModel.save torn-write contract."""
        from oap_mllib_tpu.data import io as _io

        os.makedirs(path, exist_ok=True)
        _io.atomic_save_npy(
            os.path.join(path, "components.npy"), self.components_
        )
        _io.atomic_save_npy(
            os.path.join(path, "explained_variance.npy"),
            self.explained_variance_,
        )
        _io.atomic_write_json(
            os.path.join(path, "metadata.json"),
            {"type": "PCAModel", "k": int(self.k),
             "shape": [int(v) for v in self.components_.shape],
             "version": 1},
        )

    @classmethod
    def load(cls, path: str) -> "PCAModel":
        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
        if meta.get("type") != "PCAModel":
            raise ValueError(f"not a PCAModel directory: {path}")
        cpath = os.path.join(path, "components.npy")
        comps = np.load(cpath)
        var = np.load(os.path.join(path, "explained_variance.npy"))
        expect = meta.get("shape", [None, meta["k"]])
        if comps.ndim != 2 or int(comps.shape[1]) != int(expect[1]) or (
                expect[0] is not None
                and int(comps.shape[0]) != int(expect[0])):
            raise ValueError(
                f"{cpath}: components have shape {tuple(comps.shape)}, "
                f"metadata expects {tuple(expect)} — the model directory "
                "is torn or mixed from two saves"
            )
        if var.shape[0] != comps.shape[1]:
            raise ValueError(
                f"{os.path.join(path, 'explained_variance.npy')}: "
                f"{var.shape[0]} variance ratios for {comps.shape[1]} "
                "components — the model directory is torn or mixed "
                "from two saves"
            )
        return cls(comps, var)


def _gram_kernel(cfg, d: int, tier: str, dtype) -> str:
    """The single-device Gram program the dispatch chooses, as the fit
    summaries name it."""
    return (
        "pallas" if pca_ops.use_pallas_gram(cfg.pca_kernel, d, tier, dtype)
        else "xla"
    )


def _pca_solver_cfg() -> str:
    """Validated Config.pca_solver — a typo must raise, not silently run
    eigh (the als_kernel/als_item_layout contract).  The randomized
    tuning knobs validate here too, so a bad value fails at fit() entry
    on EVERY path (fallback included) instead of after a multi-minute
    streamed covariance pass."""
    cfg = get_config()
    solver = cfg.pca_solver
    if solver not in ("auto", "eigh", "randomized"):
        raise ValueError(
            f"pca_solver must be auto|eigh|randomized, got {solver!r}"
        )
    if solver == "randomized" and (
        cfg.pca_rand_oversample < 1 or cfg.pca_rand_iters < 1
    ):
        raise ValueError(
            "pca_rand_oversample and pca_rand_iters must be >= 1"
        )
    return solver


class PCA:
    """PCA estimator. Param parity: k (number of components)."""

    def __init__(self, k: int):
        if k < 1:
            raise ValueError("k must be >= 1")
        self.k = k

    def _solve_spectrum(self, cov, d: int, timings: Timings):
        """Shared eigensolver tail (in-memory and streamed paths): full
        eigh, or the randomized top-k subspace when configured.  ``cov``
        may carry padded feature dims beyond ``d`` (model-sharded path);
        the randomized path slices them off (cov is block-diagonal with
        zero padding, so the genuine spectrum is untouched) instead of
        the eigh path's -1 diagonal demotion — subspace iteration ranks
        by |eigenvalue|, and a -1 would outrank small genuine ones.
        Returns (vals_topk, vecs (d, k), total_variance, solver_used) —
        ``solver_used`` lands in the fit summary so an A/B of the knob
        can confirm which solver actually ran (the als_kernel
        convention)."""
        solver = _pca_solver_cfg()
        if solver == "randomized":
            with phase_timer(timings, "randomized_topk"):
                cfg = get_config()
                cov_valid = cov[:d, :d]
                vals, vecs = pca_ops.topk_eigh_randomized(
                    cov_valid, self.k,
                    oversample=cfg.pca_rand_oversample,
                    iters=cfg.pca_rand_iters,
                )
                # ratio denominator: trace == eigenvalue sum, no full
                # spectrum needed
                total = float(jnp.trace(cov_valid))
                return np.asarray(vals), np.asarray(vecs), total, solver
        with phase_timer(timings, "eigh"):
            if cov.shape[0] > d:
                # padded feature dims: demote their eigenvalues below any
                # genuine one so ties at zero can't surface a padded
                # basis vector in the top-k
                cov = pca_ops.mark_padded_features(cov, d)
            vals, vecs = spans.fetch(
                jax.device_get, pca_ops.eigh_descending(cov)
            )
            vals = vals[:d]  # genuine spectrum only
            vecs = vecs[:d, : self.k]
        return vals[: self.k], vecs, float(vals.sum()), "eigh"

    def fit(self, x) -> PCAModel:
        from oap_mllib_tpu.data import sparse as _sparse
        from oap_mllib_tpu.data.stream import ChunkSource
        from oap_mllib_tpu.utils import membudget

        # validate up front, on EVERY path: a typo'd solver must fail
        # fast — before a (potentially multi-minute) streamed covariance
        # pass, and on the fallback path too (which runs NumPy eigh
        # regardless and must not silently accept garbage)
        _pca_solver_cfg()
        if isinstance(x, ChunkSource):
            return self._fit_source(x)
        if not _sparse.is_sparse(x):
            # SciPy inputs stay sparse: the chosen route densifies per
            # chunk/block at staging time (data/sparse.py)
            x = np.asarray(x)
        if x.ndim != 2:
            raise ValueError(f"expected 2-D data, got shape {x.shape}")
        n, d = x.shape
        if self.k > d:
            raise ValueError(f"k={self.k} exceeds n_features={d}")
        guard_ok = d < MAX_PCA_FEATURES
        if should_accelerate("PCA", guard_ok, reason=f"n_features={d}"):
            from oap_mllib_tpu.utils import resilience
            from oap_mllib_tpu.utils.profiling import maybe_trace

            # memory-budget route plan (utils/membudget.py): a table
            # whose resident footprint exceeds the HBM budget streams
            # the two-pass covariance instead of assuming it fits
            plan = membudget.plan_pca(n, d)
            if plan.route == membudget.ROUTE_STREAMED:
                src = ChunkSource.from_array(
                    x, chunk_rows=plan.chunk_rows
                )
                return self._fit_source(src, plan=plan)
            # degradation ladder: transient faults retry; the in-memory
            # covariance has no chunk knob, so the OOM rung re-runs the
            # same program once; a HOST OOM spills the table to disk and
            # re-enters the STREAMED covariance; then the CPU path
            stats = resilience.ResilienceStats()
            holder = {}

            def attempt(degraded):
                if holder.get("source") is not None:
                    # the spill rung fired: stream from disk
                    return self._stream_attempt(
                        holder["source"], degraded
                    )
                with maybe_trace():
                    return self._fit_tpu(x)

            def spill():
                return membudget.spill_array(
                    holder, x, None, plan.chunk_rows, "PCA"
                )

            model = resilience.resilient_fit(
                "PCA", attempt, lambda: self._fit_fallback(x),
                stats=stats, spill=spill,
            )
            resilience.merge_stats(model.summary, stats)
            membudget.record_plan(
                model.summary, plan, spilled=stats.spilled
            )
            telemetry.finalize_fit(model.summary)
            return model
        return self._fit_fallback(x)

    # -- streamed (out-of-core) path -----------------------------------------
    def _stream_attempt(self, source, degraded):
        """One streamed-fit attempt at halving level ``degraded``
        (geometric chunk width / 2^level, floored — the K-Means
        _stream_attempt contract)."""
        from oap_mllib_tpu.utils import resilience
        from oap_mllib_tpu.utils.profiling import maybe_trace
        from oap_mllib_tpu.utils.timing import x64_scope

        cfg = get_config()
        dtype = np.float64 if cfg.enable_x64 else np.float32
        src = source
        if degraded:
            rows = max(
                source.chunk_rows // (2 ** int(degraded)),
                min(resilience.OOM_CHUNK_FLOOR_ROWS, source.chunk_rows),
                1,
            )
            src = source.with_chunk_rows(rows)
        with maybe_trace(), x64_scope(cfg.enable_x64):
            return self._fit_stream_inner(src, dtype, cfg)

    def _fit_source(self, source, plan=None) -> PCAModel:
        """Out-of-core fit from a ChunkSource: two streamed passes (column
        sums, centered Gram — ops/stream_ops.covariance_streamed), device
        memory bounded by O(chunk + d^2).  Multi-process: every process
        passes its OWN shard; the moments reduce across processes.  The
        fallback path materializes the (local) source (CPU reference
        semantics assume host-RAM-resident data anyway)."""
        d = source.n_features
        if self.k > d:
            raise ValueError(f"k={self.k} exceeds n_features={d}")
        guard_ok = d < MAX_PCA_FEATURES
        if not should_accelerate("PCA", guard_ok, reason=f"n_features={d}"):
            import jax

            if jax.process_count() > 1:
                # each rank only holds its shard; a local-only fallback fit
                # would silently diverge across ranks
                raise NotImplementedError(
                    "the fallback path cannot run a multi-process streamed "
                    "fit (no cross-process reduction); use the accelerated "
                    "path or fit in-memory"
                )
            return self._fit_fallback(source.to_array())
        from oap_mllib_tpu.utils import membudget, resilience

        # route plan: source fits stream by construction; the decision,
        # estimates, and any budget breach are recorded (strict raises
        # when even the streamed footprint exceeds the budget)
        if plan is None:
            plan = membudget.plan_pca(
                source.n_rows, d, source_backing=source.backing,
                chunk_rows=source.chunk_rows,
            )
        # degradation ladder: transient source/staging faults retry the
        # two-pass covariance; device OOMs re-chunk the source at
        # chunk_rows/2^level geometrically down to the floor; a HOST OOM
        # on a memory-backed source spills it to disk and re-enters this
        # streamed route; then the CPU path (which materializes the
        # source) — single-process only (resilient_fit)
        stats = resilience.ResilienceStats()
        holder = {"source": source}

        def attempt(degraded):
            return self._stream_attempt(holder["source"], degraded)

        spill = None
        if source.backing not in ("disk", "spill"):
            spill = lambda: membudget.spill_source(holder, "PCA")  # noqa: E731
        model = resilience.resilient_fit(
            "PCA", attempt,
            lambda: self._fit_fallback(holder["source"].to_array()),
            stats=stats, spill=spill,
            max_halvings=resilience.halvings_available(source.chunk_rows),
        )
        resilience.merge_stats(model.summary, stats)
        membudget.record_plan(model.summary, plan, spilled=stats.spilled)
        telemetry.finalize_fit(model.summary)
        return model

    def _ckpt_signature(self, d: int, cfg, moments: str) -> dict:
        """Checkpoint identity (utils/checkpoint.py).  ``moments`` names
        the checkpointed accumulator layout — ``"colsum"`` (streamed
        pass-1 state) vs ``"cov"`` (in-memory covariance) — so the two
        paths can never consume each other's intermediate state.  ``k``
        is deliberately absent: the moments do not depend on it."""
        return {"d": int(d), "moments": moments,
                "x64": bool(cfg.enable_x64)}

    def _fit_stream_inner(self, source, dtype, cfg) -> PCAModel:
        from oap_mllib_tpu.ops import stream_ops

        # compute-precision policy, per attempt (the resilience ladder's
        # precision rung re-resolves to f32 on its retry); x64 pins f32
        pol = psn.resolve("pca")
        timings = Timings("pca.fit")
        cache_before = progcache.stats()
        tune_before = autotune.mark()
        d = source.n_features
        ckpt = ckpt_mod.maybe_open(
            "pca", self._ckpt_signature(d, cfg, "colsum"), timings=timings
        )
        with phase_timer(timings, "covariance_streamed"):
            tier = (
                "highest" if cfg.enable_x64
                else psn.kernel_tier(pol.name, cfg.matmul_precision)
            )
            cov, _, n = stream_ops.covariance_streamed(
                source, dtype, tier, timings=timings, policy=pol.name,
                checkpoint=ckpt,
            )
            kernel = _gram_kernel(cfg, d, tier, dtype)
        # cov is exactly (d, d) here — no model-sharding feature pad
        vals, vecs, total, solver = self._solve_spectrum(cov, d, timings)
        ratio = vals / total if total > 0 else np.zeros(self.k)
        summary = {
            "timings": timings,
            "accelerated": True,
            "streamed": True,
            "n_rows": n,
            "pca_solver": solver,
            "kernel": kernel,
            "progcache": progcache.delta(cache_before),
            "tuning": autotune.delta(tune_before),
        }
        psn.record(summary, timings, pol)
        if ckpt is not None:
            ckpt.record(summary)
        return PCAModel(vecs, ratio, summary)

    # -- accelerated path (~ PCADALImpl.train, PCADALImpl.scala:35) ----------
    def _fit_tpu(self, x: np.ndarray) -> PCAModel:
        import jax

        from oap_mllib_tpu.utils.timing import x64_scope

        cfg = get_config()
        dtype = np.float64 if cfg.enable_x64 else np.float32
        with x64_scope(cfg.enable_x64):
            return self._fit_tpu_inner(x, dtype, jax)

    def _fit_tpu_inner(self, x, dtype, jax) -> PCAModel:
        timings = Timings("pca.fit")
        cache_before = progcache.stats()
        tune_before = autotune.mark()
        cfg = get_config()
        pol = psn.resolve("pca")
        mesh = get_mesh()
        mp = mesh.shape[cfg.model_axis]
        d = x.shape[1]
        ckpt = ckpt_mod.maybe_open(
            "pca", self._ckpt_signature(d, cfg, "cov"), timings=timings
        )
        resume = ckpt.restore() if ckpt is not None else None
        restored = (
            resume is not None and resume.found
            and resume.extra.get("stage") == "cov"
        )
        if mp > 1:
            # model-sharded Gram needs d % model == 0; zero-pad feature
            # columns (they yield zero eigenvalues, which sort last) and
            # slice the component rows back after eigh
            from oap_mllib_tpu.data import sparse as _sparse

            if _sparse.is_sparse(x):
                import scipy.sparse as sp

                x = sp.csr_matrix(
                    sp.hstack(
                        [x, sp.csr_matrix(
                            (x.shape[0], (-d) % mp), dtype=x.dtype
                        )]
                    )
                )
            else:
                x = np.pad(x, ((0, 0), (0, (-d) % mp)))
        if restored:
            # the in-memory iterate state is the covariance itself
            # (stored unpadded, so it restores onto any model-parallel
            # degree): skip the table conversion AND the Gram pass, go
            # straight to the eigensolver
            cov = jnp.asarray(np.asarray(resume.arrays["cov"], dtype))
        else:
            with phase_timer(timings, "table_convert"):
                make = (
                    DenseTable.from_process_local
                    if jax.process_count() > 1
                    else DenseTable.from_numpy
                )
                # a cast or pad, where x needs one, is the constructor's,
                # inside its host_copy sub-span (data/table.py)
                table = make(x, mesh, dtype)
            with phase_timer(timings, "covariance"):
                n_rows = jnp.asarray(float(table.n_rows), dtype)
                # x64 lane pins the Gram to HIGHEST regardless of tier
                # (f64 has no bf16 fast path to buy anything with); the
                # compute-precision policy maps onto the tier otherwise
                tier = (
                    "highest" if cfg.enable_x64
                    else psn.kernel_tier(pol.name, cfg.matmul_precision)
                )
                # which Gram program the dispatch chooses, for the summary
                kernel = timings.root.attrs["kernel"] = (
                    "model_sharded" if mp > 1
                    else _gram_kernel(cfg, table.data.shape[1], tier, dtype)
                )
                if mp > 1:
                    cov, _ = pca_ops.covariance_model_sharded(
                        table.data, table.mask, n_rows, mesh, tier,
                        timings=timings, policy=pol.name,
                    )
                else:
                    cov, _ = pca_ops.covariance(
                        table.data, table.mask, n_rows, tier,
                        timings=timings, policy=pol.name,
                    )
                # the phase ends on a READY covariance: without the wait
                # the Gram's device time is booked to eigh
                spans.fetch(jax.block_until_ready, cov)
                span = spans.current_span()
                span.attrs["kernel"] = kernel
                span.attrs["rows"] = table.n_padded
                if kernel == "pallas":
                    # what the kernel issues a tile, for the reader of
                    # pca_device_roofline: passes * 2*rows*d_pad^2 / the
                    # bf16 peak is the Gram pass's own ceiling
                    span.attrs["mxu_passes"] = dict(MXU_PASSES[tier])
            if ckpt is not None:
                ckpt.maybe_write(
                    1,
                    {"cov": ckpt_mod.fetch_replicated(cov)[:d, :d]},
                    extra={"stage": "cov"}, force=True,
                )
        vals, vecs, total, solver = self._solve_spectrum(cov, d, timings)
        ratio = vals / total if total > 0 else np.zeros(self.k)
        summary = {
            "timings": timings,
            "accelerated": True,
            "mesh_shape": dict(mesh.shape),
            "pca_solver": solver,
            # absent when the covariance was restored from a checkpoint
            "kernel": timings.root.attrs.get("kernel"),
            "progcache": progcache.delta(cache_before),
            "tuning": autotune.delta(tune_before),
        }
        psn.record(summary, timings, pol)
        if ckpt is not None:
            ckpt.record(summary)
        return PCAModel(vecs, ratio, summary)

    # -- fallback path (~ vanilla mllib.feature.PCA, PCA.scala:110-116) ------
    def _fit_fallback(self, x: np.ndarray) -> PCAModel:
        from oap_mllib_tpu.data import sparse as _sparse

        timings = Timings("pca.fit")
        if _sparse.is_sparse(x):
            # the NumPy reference semantics assume dense host data
            x = x.toarray()
        with phase_timer(timings, "pca_np"):
            comps, ratio = pca_np(x, self.k)
        # the fallback always factorizes fully; recording it keeps a
        # configured-but-ineffective "randomized" visible in the summary
        return PCAModel(
            comps, ratio,
            {"timings": timings, "accelerated": False, "pca_solver": "eigh"},
        )
