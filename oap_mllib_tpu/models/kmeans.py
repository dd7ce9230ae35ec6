"""K-Means estimator with Spark-MLlib-compatible parameters.

API parity target: ``org.apache.spark.ml.clustering.KMeans`` as shimmed by
the reference (spark-3.1.1/ml/clustering/KMeans.scala) — params k, maxIter,
tol, seed, initMode (random | k-means||), initSteps, distanceMeasure — and
its model surface: clusterCenters, predict, summary (trainingCost,
numIter), save/load.

Dispatch mirrors the reference's trainWithDAL guard
(KMeans.scala:349-357): accelerated iff platform compatible AND
distanceMeasure == euclidean.  Unlike the reference, row weights do NOT
force fallback — the TPU kernel supports them natively (weights fold into
the mask vector); cosine still falls back.
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
from oap_mllib_tpu.fallback.kmeans_np import lloyd_np, predict_np
from oap_mllib_tpu.ops import kmeans_ops
from oap_mllib_tpu.ops.pallas import autotune
from oap_mllib_tpu.parallel.mesh import get_mesh
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import checkpoint as ckpt_mod
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.dispatch import should_accelerate
from oap_mllib_tpu.utils.timing import Timings, phase_timer

INIT_RANDOM = "random"
INIT_PARALLEL = "k-means||"


class KMeansSummary:
    """Training summary (~ KMeansSummary + KMeansResult,
    reference KMeansResult.java / KMeans.scala:359-368).  ``cluster_sizes``
    mirrors Spark's KMeansSummary.clusterSizes."""

    def __init__(self, training_cost: float, num_iter: int, timings: Timings,
                 accelerated: bool, cluster_sizes: Optional[np.ndarray] = None):
        self.training_cost = training_cost
        self.num_iter = num_iter
        self.timings = timings
        self.accelerated = accelerated
        self.cluster_sizes = cluster_sizes

    def __repr__(self) -> str:
        return (
            f"KMeansSummary(cost={self.training_cost:.6g}, iters={self.num_iter}, "
            f"accelerated={self.accelerated})"
        )


class KMeansModel:
    def __init__(self, cluster_centers: np.ndarray, distance_measure: str = "euclidean",
                 summary: Optional[KMeansSummary] = None):
        self.cluster_centers_ = np.asarray(cluster_centers)
        self.distance_measure = distance_measure
        self.summary = summary
        # device-copy cache (serving/registry.pin): identity-keyed on
        # the host array, so scoring calls never re-upload the centers
        # and a refit (fresh array) re-stages exactly once
        self._dev_cache: dict = {}

    @property
    def k(self) -> int:
        return self.cluster_centers_.shape[0]

    # element budget for the live buffers in predict/cost — the (chunk, k)
    # distance matrix AND the (chunk, d) input chunk (a fixed ROW count
    # would blow up at large k; bounding only k would blow up at large d);
    # the same bound the training loop gets from auto_row_chunks
    _PREDICT_BUDGET = kmeans_ops.SCORE_BUDGET_ELEMS

    def _score_chunk_rows(self) -> int:
        return kmeans_ops.rows_per_chunk(
            self.k, self.cluster_centers_.shape[1],
            budget=self._PREDICT_BUDGET,
        )

    def _centers_dev(self):
        """The pinned device copy of the centers (serving/registry.pin)
        — staged once per model lifetime, re-staged only on refit."""
        from oap_mllib_tpu.serving.registry import pin

        return pin(self._dev_cache, "centers", self.cluster_centers_)

    def _predict_euclidean(self, x: np.ndarray) -> np.ndarray:
        """Bucketed serving-program scoring (serving/batcher.py):
        fixed-width row slices against the PINNED centers, each slice
        rounded onto its geometric bucket — every full chunk shares one
        compiled shape, the tail its bucket's, and no call re-uploads
        the centers."""
        from oap_mllib_tpu.serving import batcher

        c = self._centers_dev()
        rows = self._score_chunk_rows()
        return np.concatenate([
            batcher.assign_kmeans(c, x[lo : lo + rows])
            for lo in range(0, max(len(x), 1), rows)
        ])

    def predict(self, x) -> np.ndarray:
        """Nearest-center assignment (the shim's transform/predict surface).
        Accepts a ChunkSource for out-of-core scoring (labels are O(n)
        host memory); disk-backed chunks route through the SAME bucketed
        serving program as the ndarray path, so the results are
        bit-identical and the compiled-shape count stays bounded."""
        from oap_mllib_tpu.data.stream import ChunkSource

        if isinstance(x, ChunkSource):
            if self.distance_measure == "euclidean":
                parts = [
                    self._predict_euclidean(
                        np.asarray(
                            c[:v], dtype=self.cluster_centers_.dtype
                        )
                    )
                    for c, v in x
                ]
            else:
                parts = [self.predict(c[:v]) for c, v in x]
            if not parts:  # empty source: same contract as an empty array
                return self.predict(np.zeros((0, x.n_features)))
            return np.concatenate(parts)
        x = np.asarray(x, dtype=self.cluster_centers_.dtype)
        if self.distance_measure == "euclidean" and x.shape[0] >= 1:
            return self._predict_euclidean(x)
        return predict_np(x, self.cluster_centers_, self.distance_measure)

    def transform(self, x: np.ndarray) -> np.ndarray:
        return self.predict(x)

    def partial_fit(self, x, sample_weight=None) -> "KMeansModel":
        """Mini-batch Lloyd delta (online/minibatch.py): ONE decayed,
        count-weighted assignment pass over the arriving chunks through
        the streamed-pass machinery (stream_ops.streamed_accumulate) —
        no re-init, no convergence loop.  The update is compute-then
        -swap: the centers array is replaced atomically at the end, so
        a fault mid-pass leaves the model (and its served pin) exactly
        as it was.  Commits re-pin any serving handle in place
        (serving/registry.repin_model) — in-flight requests keep their
        handle, the next batch scores the new centers.  Returns
        ``self`` (mutated)."""
        from oap_mllib_tpu.online import minibatch

        return minibatch.partial_fit_kmeans(self, x, sample_weight)

    def compute_cost(self, x) -> float:
        from oap_mllib_tpu.data.stream import ChunkSource

        if isinstance(x, ChunkSource):
            return float(sum(self.compute_cost(c[:v]) for c, v in x))
        x = np.asarray(x, dtype=self.cluster_centers_.dtype)
        if self.distance_measure != "euclidean":
            from oap_mllib_tpu.fallback.kmeans_np import _sq_dists

            d = _sq_dists(x, self.cluster_centers_, self.distance_measure)
            return float(np.sum(np.min(d, axis=1)))
        c = self._centers_dev()  # pinned — no per-call re-upload
        rows = self._score_chunk_rows()
        return float(sum(
            float(jnp.sum(jnp.min(
                kmeans_ops.pairwise_sq_dists(
                    jnp.asarray(x[lo : lo + rows]), c
                ), axis=1
            )))
            for lo in range(0, len(x), rows)
        ))

    def to_pmml(self, path: str) -> None:
        """Export as a PMML 4.3 ClusteringModel (~ Spark's
        KMeansModel.write.format("pmml"), exercised by the reference's
        IntelKMeansSuite "pmml export" test)."""
        import xml.etree.ElementTree as ET

        d = self.cluster_centers_.shape[1]
        root = ET.Element(
            "PMML",
            {"version": "4.3", "xmlns": "http://www.dmg.org/PMML-4_3"},
        )
        header = ET.SubElement(root, "Header", {"description": "k-means clustering"})
        ET.SubElement(header, "Application", {"name": "oap-mllib-tpu"})
        dd = ET.SubElement(root, "DataDictionary", {"numberOfFields": str(d)})
        for j in range(d):
            ET.SubElement(
                dd, "DataField",
                {"name": f"field_{j}", "optype": "continuous", "dataType": "double"},
            )
        cm = ET.SubElement(
            root, "ClusteringModel",
            {
                "modelName": "k-means",
                "functionName": "clustering",
                "modelClass": "centerBased",
                "numberOfClusters": str(self.k),
            },
        )
        ms = ET.SubElement(cm, "MiningSchema")
        for j in range(d):
            ET.SubElement(ms, "MiningField", {"name": f"field_{j}"})
        ET.SubElement(
            cm, "ComparisonMeasure", {"kind": "distance"}
        ).append(ET.Element("squaredEuclidean"))
        for j in range(d):
            ET.SubElement(
                cm, "ClusteringField", {"field": f"field_{j}", "compareFunction": "absDiff"}
            )
        for i, center in enumerate(self.cluster_centers_):
            cl = ET.SubElement(cm, "Cluster", {"name": f"cluster_{i}", "id": str(i)})
            arr = ET.SubElement(cl, "Array", {"n": str(d), "type": "real"})
            arr.text = " ".join(repr(float(v)) for v in center)
        ET.ElementTree(root).write(path, xml_declaration=True, encoding="utf-8")

    # -- persistence (~ Spark ML read/write, tested in IntelKMeansSuite) -----
    def save(self, path: str) -> None:
        """Atomic write (tmp+``os.replace`` per file, metadata last —
        data/io primitives): a kill mid-save leaves either the previous
        model or arrays the metadata does not reference yet, never a
        torn file the next load would misread."""
        from oap_mllib_tpu.data import io as _io

        os.makedirs(path, exist_ok=True)
        _io.atomic_save_npy(
            os.path.join(path, "centers.npy"), self.cluster_centers_
        )
        _io.atomic_write_json(
            os.path.join(path, "metadata.json"),
            {"type": "KMeansModel",
             "distance_measure": self.distance_measure,
             "k": int(self.k),
             "shape": [int(v) for v in self.cluster_centers_.shape],
             "version": 1},
        )

    @classmethod
    def load(cls, path: str) -> "KMeansModel":
        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
        if meta.get("type") != "KMeansModel":
            raise ValueError(f"not a KMeansModel directory: {path}")
        cpath = os.path.join(path, "centers.npy")
        centers = np.load(cpath)
        expect = meta.get("shape", [meta["k"], None])
        if centers.ndim != 2 or int(centers.shape[0]) != int(expect[0]) or (
                expect[1] is not None
                and int(centers.shape[1]) != int(expect[1])):
            raise ValueError(
                f"{cpath}: centers have shape {tuple(centers.shape)}, "
                f"metadata expects {tuple(expect)} — the model directory "
                "is torn or mixed from two saves"
            )
        return cls(centers, meta["distance_measure"])


class KMeans:
    """K-Means estimator.

    Parameters mirror Spark ML (reference shim KMeans.scala param defaults):
    k=2, max_iter=20, tol=1e-4, init_mode="k-means||", init_steps=2,
    distance_measure="euclidean", seed derived from class name there, plain
    int here.
    """

    def __init__(
        self,
        k: int = 2,
        max_iter: int = 20,
        tol: float = 1e-4,
        seed: Optional[int] = None,
        init_mode: str = INIT_PARALLEL,
        init_steps: int = 2,
        distance_measure: str = "euclidean",
    ):
        if k < 1:
            raise ValueError("k must be >= 1")
        if max_iter < 0:
            raise ValueError("max_iter must be >= 0")
        if init_mode not in (INIT_RANDOM, INIT_PARALLEL):
            raise ValueError(f"init_mode must be '{INIT_RANDOM}' or '{INIT_PARALLEL}'")
        if distance_measure not in ("euclidean", "cosine"):
            raise ValueError("distance_measure must be 'euclidean' or 'cosine'")
        if init_steps < 1:
            raise ValueError("init_steps must be >= 1")
        self.k = k
        self.max_iter = max_iter
        self.tol = tol
        # None = Config.seed (the OAP_MLLIB_TPU_SEED default for
        # estimators that do not set one — docs/configuration.md)
        self.seed = get_config().seed if seed is None else seed
        self.init_mode = init_mode
        self.init_steps = init_steps
        self.distance_measure = distance_measure

    def fit(self, x, sample_weight: Optional[np.ndarray] = None) -> KMeansModel:
        from oap_mllib_tpu.data import sparse as _sparse
        from oap_mllib_tpu.data.stream import ChunkSource
        from oap_mllib_tpu.utils import membudget

        if isinstance(x, ChunkSource):
            return self._fit_source(x, sample_weight)
        if not _sparse.is_sparse(x):
            # SciPy inputs stay sparse here: the chosen route densifies
            # per chunk/block at staging time (data/sparse.py)
            x = np.asarray(x)
        if x.ndim != 2:
            raise ValueError(f"expected 2-D data, got shape {x.shape}")
        if x.shape[0] < 1:
            raise ValueError("empty input")
        guard_ok = self.distance_measure == "euclidean"
        accelerated = should_accelerate(
            "KMeans", guard_ok, reason=f"distance_measure={self.distance_measure}"
        )
        if accelerated:
            from oap_mllib_tpu.utils import resilience
            from oap_mllib_tpu.utils.profiling import maybe_trace

            # memory-budget route plan (utils/membudget.py): an ndarray
            # whose working set exceeds the HBM budget streams through
            # the prefetch pipeline instead of silently assuming it fits
            # priced per device: on a mesh each holds one row shard
            shards = get_mesh().shape[get_config().data_axis]
            plan = membudget.plan_kmeans(
                x.shape[0], x.shape[1], self.k,
                row_chunks_hint=kmeans_ops.auto_row_chunks(
                    -(-x.shape[0] // shards), self.k
                ),
                shards=shards,
            )
            if plan.route == membudget.ROUTE_STREAMED:
                src = ChunkSource.from_array(
                    x, chunk_rows=plan.chunk_rows
                )
                return self._fit_source(src, sample_weight, plan=plan)
            # degradation ladder (utils/resilience.py): transient faults
            # retry the fit; device OOMs walk the geometric halved-chunk
            # rungs; a HOST OOM spills the table to disk and re-enters
            # the streamed route; the final rung is the same CPU path
            # the static gate falls back to
            stats = resilience.ResilienceStats()
            holder = {}

            def attempt(degraded):
                if holder.get("source") is not None:
                    # the spill rung fired: the table now lives on disk
                    return self._stream_attempt(
                        holder["source"], holder.get("weights"), degraded
                    )
                with maybe_trace():
                    return self._fit_tpu(x, sample_weight, degraded)

            def spill():
                return membudget.spill_array(
                    holder, x, sample_weight, plan.chunk_rows, "KMeans"
                )

            model = resilience.resilient_fit(
                "KMeans", attempt,
                lambda: self._fit_fallback(x, sample_weight),
                stats=stats, spill=spill,
            )
            resilience.merge_stats(model.summary, stats)
            membudget.record_plan(
                model.summary, plan, spilled=stats.spilled
            )
            telemetry.finalize_fit(model.summary)
            return model
        return self._fit_fallback(x, sample_weight)

    # -- streamed (out-of-core) path -----------------------------------------
    def _stream_attempt(self, source, sample_weight, degraded):
        """One streamed-fit attempt at halving level ``degraded`` (the
        resilience ladder's geometric OOM rung: chunk width / 2^level,
        floored at OOM_CHUNK_FLOOR_ROWS — never widened)."""
        from oap_mllib_tpu.config import get_config as _gc
        from oap_mllib_tpu.utils import resilience
        from oap_mllib_tpu.utils.profiling import maybe_trace
        from oap_mllib_tpu.utils.timing import x64_scope

        cfg = _gc()
        dtype = np.float64 if cfg.enable_x64 else np.float32
        src, w = source, sample_weight
        if degraded:
            rows = max(
                source.chunk_rows // (2 ** int(degraded)),
                min(resilience.OOM_CHUNK_FLOOR_ROWS, source.chunk_rows),
                1,
            )
            src = source.with_chunk_rows(rows)
            if w is not None:
                w = w.with_chunk_rows(rows)
        with maybe_trace(), x64_scope(cfg.enable_x64):
            return self._fit_stream_inner(src, w, dtype, cfg)

    def _fit_source(self, source, sample_weight, plan=None) -> KMeansModel:
        """Out-of-core fit from a ChunkSource (ops/stream_ops.py): device
        memory bounded by O(chunk), one pass per Lloyd iteration.  Multi
        -process: every process passes its OWN shard as a local source;
        sums/counts/init state reduce across processes (host-mediated, the
        DCN analog of the mesh path's ICI psums).  ``sample_weight`` may
        be a width-1 ChunkSource chunked like the data, or an in-memory
        array (wrapped automatically).  The fallback path materializes
        the (local) source — the CPU reference semantics assume
        host-RAM-resident data anyway."""
        from oap_mllib_tpu.data.stream import ChunkSource

        if sample_weight is not None and not isinstance(sample_weight, ChunkSource):
            sample_weight = ChunkSource.from_array(
                np.asarray(sample_weight).reshape(-1, 1),
                chunk_rows=source.chunk_rows,
            )
        # validate up front so BOTH branches (accelerated and fallback)
        # reject malformed weight sources with a clear error; the outcome
        # is synced across ranks so a single bad shard fails the world
        # together instead of leaving peers in process_allgather
        if sample_weight is not None:
            from oap_mllib_tpu.ops.stream_ops import (
                _check_weight_source,
                _checked_entry,
            )

            _checked_entry(
                lambda: _check_weight_source(source, sample_weight)
            )
        guard_ok = self.distance_measure == "euclidean"
        accelerated = should_accelerate(
            "KMeans", guard_ok, reason=f"distance_measure={self.distance_measure}"
        )
        if not accelerated:
            import jax

            if jax.process_count() > 1:
                # each rank only holds its shard; a local-only fallback fit
                # would silently diverge across ranks
                raise NotImplementedError(
                    "the fallback path cannot run a multi-process streamed "
                    "fit (no cross-process reduction); use the accelerated "
                    "path or fit in-memory"
                )
            w_arr = (
                sample_weight.to_array().reshape(-1)
                if sample_weight is not None else None
            )
            return self._fit_fallback(source.to_array(), w_arr)
        from oap_mllib_tpu.utils import membudget, resilience

        # route plan: source fits stream by construction; the planner
        # records the decision + estimates (and raises under strict when
        # even the streamed footprint exceeds the budget)
        if plan is None:
            plan = membudget.plan_kmeans(
                source.n_rows, source.n_features, self.k,
                source_backing=source.backing,
                chunk_rows=source.chunk_rows,
            )
        # degradation ladder: transient source/staging faults retry the
        # fit; device OOMs re-chunk the source (and its lockstep weight
        # source) at chunk_rows/2^level geometrically down to the floor;
        # a HOST OOM on a memory-backed source spills it to disk and
        # re-enters this same streamed route; then the CPU path (which
        # materializes the source) is the final rung.  Multi-process
        # worlds bypass the ladder — the fail-fast static-world contract
        # (docs/distributed.md) — resilient_fit handles that.
        stats = resilience.ResilienceStats()
        holder = {"source": source, "weights": sample_weight}

        def attempt(degraded):
            return self._stream_attempt(
                holder["source"], holder.get("weights"), degraded
            )

        def fallback():
            w = holder.get("weights")
            w_arr = w.to_array().reshape(-1) if w is not None else None
            return self._fit_fallback(holder["source"].to_array(), w_arr)

        spill = None
        if source.backing not in ("disk", "spill"):
            spill = lambda: membudget.spill_source(holder, "KMeans")  # noqa: E731
        model = resilience.resilient_fit(
            "KMeans", attempt, fallback, stats=stats, spill=spill,
            max_halvings=resilience.halvings_available(source.chunk_rows),
        )
        resilience.merge_stats(model.summary, stats)
        membudget.record_plan(model.summary, plan, spilled=stats.spilled)
        telemetry.finalize_fit(model.summary)
        return model

    def _ckpt_signature(self, d: int, cfg) -> dict:
        """Checkpoint identity (utils/checkpoint.py): the parameters that
        define WHICH optimization the iterate state belongs to.  World
        size, chunk geometry, and the precision policy are deliberately
        absent — all three may legitimately change across a preemption
        (that is the elastic-worlds point)."""
        return {
            "k": self.k, "d": int(d), "init_mode": self.init_mode,
            "init_steps": self.init_steps, "seed": int(self.seed),
            "tol": float(self.tol), "distance": self.distance_measure,
            "x64": bool(cfg.enable_x64),
        }

    def _fit_stream_inner(self, source, sample_weight, dtype, cfg) -> KMeansModel:
        from oap_mllib_tpu.ops import stream_ops

        # compute-precision policy (utils/precision.py): resolved per
        # attempt so the resilience ladder's f32-degradation scope takes
        # effect on a retry; the legacy kernel tier maps off it
        pol = psn.resolve("kmeans")
        tier = psn.kernel_tier(pol.name, cfg.matmul_precision)
        # the route validates kmeans_kernel/ring_reduction on EVERY
        # accelerated fit: a typo'd value raises here too, even though
        # the streamed passes are always the chunked XLA programs (the
        # ring engages in their multi-process per-pass reductions —
        # stream_ops._ring_mesh)
        route = kmeans_ops.lloyd_route(
            cfg, None, None, source.n_features, self.k, dtype, tier
        )
        timings = Timings("kmeans.fit")
        cache_before = progcache.stats()
        tune_before = autotune.mark()
        ckpt = ckpt_mod.maybe_open(
            "kmeans", self._ckpt_signature(source.n_features, cfg),
            timings=timings,
        )
        resume = ckpt.restore() if ckpt is not None else None
        with phase_timer(timings, "init_centers"):
            if resume is not None and resume.found:
                # the restored centroids ARE the iterate: the init passes
                # (reservoir / k-means||) are part of the work a resumed
                # fit does not redo
                centers0 = np.asarray(resume.arrays["centers"], dtype)
            elif self.init_mode == INIT_RANDOM:
                centers0 = stream_ops.reservoir_sample(
                    source, self.k, self.seed, timings=timings
                )
            else:
                centers0 = stream_ops.init_kmeans_parallel_streamed(
                    source, self.k, self.seed, self.init_steps, dtype,
                    weights=sample_weight, validated=True, timings=timings,
                    policy=pol.name,
                )
        with phase_timer(timings, "lloyd_loop"):
            centers, n_iter, cost, counts = stream_ops.lloyd_run_streamed(
                source, centers0, self.max_iter, self.tol, dtype,
                tier, weights=sample_weight, validated=True,
                timings=timings, policy=pol.name, checkpoint=ckpt,
                resume=resume,
            )
        summary = KMeansSummary(
            float(cost), int(n_iter), timings, accelerated=True,
            cluster_sizes=np.asarray(counts),
        )
        summary.streamed = True
        summary.kernel = route.kernel
        summary.progcache = progcache.delta(cache_before)
        summary.tuning = autotune.delta(tune_before)
        psn.record(summary, timings, pol)
        if ckpt is not None:
            ckpt.record(summary)
        return KMeansModel(np.asarray(centers), self.distance_measure, summary)

    # -- accelerated path (~ KMeansDALImpl.train, KMeansDALImpl.scala:35) ----
    def _fit_tpu(self, x: np.ndarray, sample_weight: Optional[np.ndarray],
                 degraded: bool = False) -> KMeansModel:
        from oap_mllib_tpu.utils.timing import x64_scope

        cfg = get_config()
        dtype = np.float64 if cfg.enable_x64 else np.float32
        with x64_scope(cfg.enable_x64):
            return self._fit_tpu_inner(x, sample_weight, dtype, degraded)

    def _fit_tpu_inner(self, x, sample_weight, dtype,
                       degraded: bool = False) -> KMeansModel:
        cfg = get_config()
        # compute-precision policy, resolved per attempt (the resilience
        # ladder's precision rung re-resolves to f32 on its retry)
        pol = psn.resolve("kmeans")
        timings = Timings("kmeans.fit")
        cache_before = progcache.stats()
        tune_before = autotune.mark()
        mesh = get_mesh()
        mp = mesh.shape[cfg.model_axis]
        d_orig = x.shape[1]
        if d_orig % mp and kmeans_ops.lloyd_route(
            cfg, mesh, None, d_orig, self.k, dtype,
            psn.kernel_tier(pol.name, cfg.matmul_precision),
        ).kernel == "model_sharded":
            # model-sharded Lloyd needs d % model == 0; zero-pad feature
            # columns (zero in data AND centroids — no distance or move
            # contribution) and slice them back off the final centers.
            # Skipped when no padding is needed or when "xla" forces the
            # data-parallel route — np.pad would copy the whole dataset.
            from oap_mllib_tpu.data import sparse as _sparse

            if _sparse.is_sparse(x):
                # zero columns add no stored entries in CSR
                import scipy.sparse as sp

                x = sp.csr_matrix(
                    sp.hstack(
                        [x, sp.csr_matrix(
                            (x.shape[0], (-d_orig) % mp), dtype=x.dtype
                        )]
                    )
                )
            else:
                x = np.pad(x, ((0, 0), (0, (-d_orig) % mp)))
        with phase_timer(timings, "table_convert"):
            # multi-process: each host contributes its local shard
            # (README multi-host flow); single-process: the full table
            make = (
                DenseTable.from_process_local
                if jax.process_count() > 1
                else DenseTable.from_numpy
            )
            # a cast or pad, where x needs one, is the constructor's,
            # inside its host_copy sub-span (data/table.py)
            table = make(x, mesh, dtype)
            weights = table.mask
            if sample_weight is not None:
                # collective path: multi-host shards pad per process, so the
                # weights must be stitched with the mask's exact layout
                weights = table.align_weights(sample_weight, mesh)
        ckpt = ckpt_mod.maybe_open(
            "kmeans", self._ckpt_signature(d_orig, cfg), timings=timings
        )
        resume = ckpt.restore() if ckpt is not None else None
        with phase_timer(timings, "init_centers"):
            if resume is not None and resume.found:
                # restored centroids are stored at d_orig; re-pad the
                # feature axis to whatever the CURRENT mesh needs (the
                # model-parallel degree may have changed with the world)
                c = np.asarray(resume.arrays["centers"], dtype)
                centers0 = np.pad(
                    c, ((0, 0), (0, x.shape[1] - d_orig))
                )
            elif self.init_mode == INIT_RANDOM:
                centers0 = kmeans_ops.init_random(
                    table.data, table.n_rows, self.k, self.seed,
                    index_map=table.valid_to_padded,
                ).astype(dtype)
            else:
                centers0 = kmeans_ops.init_kmeans_parallel(
                    table.data, weights, table.n_rows, self.k, self.seed,
                    self.init_steps, index_map=table.valid_to_padded,
                ).astype(dtype)
        with phase_timer(timings, "lloyd_loop"):
            centers, n_iter, cost, counts = self._run_lloyd(
                table, weights, centers0, dtype, cfg, mesh, timings,
                degraded=degraded, pol=pol, ckpt=ckpt, resume=resume,
                d_orig=d_orig,
            )
            # the model's arrays come back inside the phase's fetch leaf
            centers, n_iter, cost, counts = spans.fetch(
                jax.device_get, (centers, n_iter, cost, counts)
            )
            centers = np.asarray(centers)[:, :d_orig]
            n_iter = int(n_iter)
            cost = float(cost)
        summary = KMeansSummary(
            cost, n_iter, timings, accelerated=True, cluster_sizes=counts,
        )
        summary.progcache = progcache.delta(cache_before)
        summary.tuning = autotune.delta(tune_before)
        summary.kernel = timings.root.attrs["kernel"]
        psn.record(summary, timings, pol)
        if ckpt is not None:
            ckpt.record(summary)
        return KMeansModel(centers, self.distance_measure, summary)

    def _run_lloyd(self, table, weights, centers0, dtype, cfg, mesh,
                   timings=None, degraded=False, pol=None, ckpt=None,
                   resume=None, d_orig=None):
        """Run the hot loop on the route kmeans_ops.lloyd_route picks for
        this fit — whole, or in checkpointed segments."""
        # the compute-precision policy maps onto the legacy kernel tier
        # (utils/precision.kernel_tier: f32 keeps matmul_precision, tf32
        # the bf16_3x "high" tier, bf16 the single-pass "default" tier) so
        # the route prices it like the tier it runs at
        pol = pol or psn.resolve("kmeans")
        tier = psn.kernel_tier(pol.name, cfg.matmul_precision)
        route = kmeans_ops.lloyd_route(
            cfg, mesh, table.n_padded, table.data.shape[1], self.k, dtype,
            tier, degraded=degraded, checkpoint=ckpt is not None,
        )
        if timings is not None:
            # which Lloyd program the route named, for the summary
            timings.root.attrs["kernel"] = route.kernel
        tol = jnp.asarray(self.tol, dtype)

        def run_iters(c0, iters):
            return kmeans_ops.lloyd_run(
                table.data, weights, c0, iters, tol,
                route.row_chunks, tier, timings, policy=pol.name, mesh=mesh,
                data_axis=cfg.data_axis, model_axis=cfg.model_axis,
                accumulate=route.kernel, **route.geometry,
            )

        if ckpt is None:
            return run_iters(centers0, self.max_iter)
        return self._run_lloyd_segmented(
            run_iters, centers0, ckpt, resume, d_orig
        )

    def _run_lloyd_segmented(self, run_iters, centers0, ckpt, resume,
                             d_orig):
        """Checkpoint-armed in-memory Lloyd: run the compiled loop in
        ``checkpoint_interval``-sized segments and checkpoint the
        centroids + completed-iteration count between them.  The centroid
        SEQUENCE is identical to the unsegmented loop (each iteration is
        a pure function of the previous centers); the one observable
        divergence is a fit that converges exactly on a segment boundary
        running one extra (sub-tol) iteration — a resumed fit replays
        the same segment schedule, so kill-and-resume stays bit-identical
        against an uninterrupted checkpoint-armed run."""
        done = 0
        converged = False
        if resume is not None and resume.found:
            done = min(int(resume.step), self.max_iter)
            converged = bool(resume.extra.get("converged", False))
        centers = centers0
        ran_segment = False
        while done < self.max_iter and not converged:
            seg = min(ckpt.interval, self.max_iter - done)
            centers, n_it, cost, counts = run_iters(centers, seg)
            ran_segment = True
            done += int(n_it)
            converged = int(n_it) < seg
            ckpt.maybe_write(
                done,
                {"centers": ckpt_mod.fetch_replicated(centers)[:, :d_orig]},
                extra={"converged": converged}, force=True,
            )
        if not ran_segment:
            # fully restored (converged or out of budget): one
            # zero-iteration call computes cost/counts for the summary
            centers, _, cost, counts = run_iters(centers, 0)
        return centers, done, cost, counts

    # -- fallback path (~ trainWithML, KMeans.scala:355) ---------------------
    def _fit_fallback(self, x: np.ndarray, sample_weight: Optional[np.ndarray]) -> KMeansModel:
        from oap_mllib_tpu.data import sparse as _sparse

        timings = Timings("kmeans.fit")
        if _sparse.is_sparse(x):
            # the NumPy reference semantics assume dense host data
            x = x.toarray()
        x = x.astype(np.float64)
        with phase_timer(timings, "init_centers"):
            if self.init_mode == INIT_RANDOM:
                centers0 = kmeans_ops.init_random(x, x.shape[0], self.k, self.seed)
            else:
                # host k-means++ over full data as the || analog (small-data path)
                rng = np.random.default_rng(self.seed)
                w = np.ones(x.shape[0]) if sample_weight is None else np.asarray(sample_weight)
                centers0 = kmeans_ops._weighted_kmeans_pp(x, w, self.k, rng)
        with phase_timer(timings, "lloyd_loop"):
            centers, n_iter, cost = lloyd_np(
                x, centers0, self.max_iter, self.tol, sample_weight, self.distance_measure
            )
        assign = predict_np(x, centers, self.distance_measure)
        w = np.ones(len(x)) if sample_weight is None else np.asarray(sample_weight)
        sizes = np.zeros(self.k)
        np.add.at(sizes, assign, w)
        summary = KMeansSummary(
            cost, n_iter, timings, accelerated=False, cluster_sizes=sizes
        )
        return KMeansModel(centers, self.distance_measure, summary)
