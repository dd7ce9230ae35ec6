"""ALS estimator with Spark-MLlib-compatible parameters.

API parity target: ``org.apache.spark.ml.recommendation.ALS`` as shimmed by
the reference (spark-3.1.1/ml/recommendation/ALS.scala): params rank,
maxIter, regParam, alpha, implicitPrefs, seed; model surface userFactors /
itemFactors and pairwise prediction.

Dispatch: the reference accelerates ONLY implicit-feedback ALS
(ALS.scala:925) and falls back to Spark otherwise.  Here both implicit and
explicit run accelerated (the TPU kernels cover both); the fallback NumPy
path remains for ``device=cpu`` or failed platform checks.

Ids: like the reference (ALSDALImpl.scala:62-70 computes nUsers/nItems via
RDD max), ids are dense non-negative ints; n_users/n_items default to
max+1 and may be passed explicitly.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from oap_mllib_tpu import telemetry
from oap_mllib_tpu.fallback import als_np
from oap_mllib_tpu.ops import als_ops
from oap_mllib_tpu.ops.pallas import autotune
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import checkpoint as ckpt_mod
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.dispatch import should_accelerate
from oap_mllib_tpu.utils.timing import Timings, phase_timer


class ALSModel:
    """Trained ALS factors.

    User factors may be held as rank-local device shards (block-sharded
    over the mesh with per-block offsets — the ALSResult.cUserOffset
    bookkeeping of the reference, ALSDALImpl.cpp:529-575) and are only
    gathered to host on first access of ``user_factors_``.  In a
    multi-process world that gather is a COLLECTIVE: every process must
    touch ``user_factors_`` (or predict/save) together, mirroring how the
    reference reassembles factor RDDs with a cluster-wide job
    (ALSDALImpl.scala:124-164).
    """

    def __init__(self, user_factors: Optional[np.ndarray],
                 item_factors: Optional[np.ndarray],
                 summary: Optional[dict] = None, *,
                 sharded_user: Optional[tuple] = None,
                 sharded_item: Optional[tuple] = None):
        if (user_factors is None) == (sharded_user is None):
            raise ValueError("pass exactly one of user_factors / sharded_user")
        if (item_factors is None) == (sharded_item is None):
            raise ValueError("pass exactly one of item_factors / sharded_item")
        self._user_factors = (
            None if user_factors is None else np.asarray(user_factors)
        )
        self._item_factors = (
            None if item_factors is None else np.asarray(item_factors)
        )
        # each: (blocks jax.Array (world*per, r) block-sharded, offsets, per)
        self._sharded_user = sharded_user
        self._sharded_item = sharded_item
        self.summary = summary or {}
        # device-copy cache (serving/registry.pin): the top-k target
        # table pins across chunks AND across calls — one upload per
        # factor table per model lifetime, not one per recommend call
        self._dev_cache: dict = {}

    @property
    def user_factors_(self) -> np.ndarray:
        if self._user_factors is None:
            self._user_factors = self._gather_blocks(self._sharded_user)
        return self._user_factors

    @property
    def item_factors_(self) -> np.ndarray:
        """Item factors; block-sharded fits (als_item_layout="sharded")
        gather on first access — a COLLECTIVE in multi-process worlds,
        same contract as user_factors_."""
        if self._item_factors is None:
            self._item_factors = self._gather_blocks(self._sharded_item)
        return self._item_factors

    @staticmethod
    def _gather_blocks(shard: tuple) -> np.ndarray:
        """On-demand gather of block-sharded factors (collective when the
        blocks span processes).  ``shard`` = (blocks, offsets, per_block);
        block b's real rows [offsets[b], offsets[b+1]) sit at padded rows
        [b*per_block, ...) — the ALSResult cUserOffset bookkeeping of the
        reference, ALSDALImpl.cpp:529-575."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        xb, offsets, per = shard
        if not xb.is_fully_addressable:
            mesh = xb.sharding.mesh
            xb = progcache.get_or_build(
                "als.gather_replicated",
                (progcache.mesh_fingerprint(mesh),),
                lambda: jax.jit(
                    lambda a: a, out_shardings=NamedSharding(mesh, P())
                ),
            )(xb)
        xb = np.asarray(xb)
        rank = xb.shape[1]
        n = int(offsets[-1])
        x = np.zeros((n, rank), np.float32)
        for b in range(len(offsets) - 1):
            lo, hi = int(offsets[b]), int(offsets[b + 1])
            x[lo:hi] = xb[b * per : b * per + (hi - lo)]
        return x

    @property
    def rank(self) -> int:
        if self._item_factors is not None:
            return self._item_factors.shape[1]
        return self._sharded_item[0].shape[1]

    def predict(self, users: np.ndarray, items: np.ndarray) -> np.ndarray:
        """Predicted preference/rating for (user, item) pairs
        (~ ALSModel.transform's dot-product predictions)."""
        users = np.asarray(users, dtype=np.int32)
        items = np.asarray(items, dtype=np.int32)
        return np.asarray(
            als_ops.predict_pairs(
                jnp.asarray(self.user_factors_),
                jnp.asarray(self.item_factors_),
                jnp.asarray(users),
                jnp.asarray(items),
            )
        )

    def _top_k_scores(self, query: np.ndarray, targets: np.ndarray, n: int,
                      row_chunk: int = 0, with_scores: bool = True):
        """Top-n (ids, scores) per query row, chunked over query rows so
        the (n_query, n_targets) score matrix never materializes (the
        reference blocks its recommendForAll the same way —
        ALS.scala:383-401 blockify — because the full cross product is
        quadratic in memory).  ``row_chunk`` 0 sizes chunks from the
        shared live-buffer budget over the score block AND the query
        chunk (kmeans_ops.rows_per_chunk) — a fixed row count would blow
        up against a huge target side, and a score-only bound against a
        wide query side.  ``with_scores=False`` skips the host transfer
        of the float score blocks entirely (ids-only callers should not
        pay a second device->host copy); the scores slot is then None.

        Scoring routes through the serving batcher (serving/batcher.py):
        the target table PINS on-device across chunks and across calls
        (the model's device cache — one upload per table per model
        lifetime), the tail chunk rounds onto its geometric bucket, and
        the pdot policy stays the serving default (f32 = HIGHEST,
        bit-compatible: the returned scores must match predict() —
        TPU's default bf16 matmul drifts them ~1e-3 and can swap
        near-tie rankings, caught on hardware, round 5).

        ``n`` is clamped to the target count, like Spark's
        recommendForAll* which just returns fewer rows when asked for
        more than exist — without the clamp lax.top_k raises an opaque
        XLA error on an oversized request."""
        from oap_mllib_tpu.ops.kmeans_ops import rows_per_chunk
        from oap_mllib_tpu.serving import batcher
        from oap_mllib_tpu.serving.registry import pin

        if n < 0:
            raise ValueError(f"top-k count must be >= 0, got {n}")
        n = min(int(n), targets.shape[0])
        if query.shape[0] == 0:
            return (
                np.zeros((0, n), np.int32),
                np.zeros((0, n), np.float32) if with_scores else None,
            )
        rows = row_chunk or rows_per_chunk(
            targets.shape[0], query.shape[1]
        )
        if targets is self._item_factors:
            tj = pin(self._dev_cache, "targets:item", targets)
        elif targets is self._user_factors:
            tj = pin(self._dev_cache, "targets:user", targets)
        else:  # a transient target table (tests, subsets): stage once
            tj = batcher.stage(np.asarray(targets, np.float32))
        ids, scores = [], []
        for lo in range(0, query.shape[0], rows):
            q = np.asarray(query[lo : lo + rows], np.float32)
            nv = q.shape[0]
            if nv < rows:
                # tail chunk rounds onto its bucket — one extra compiled
                # shape at most, whatever the query size
                q, _ = batcher.bucket_batch(q)
            s, i = batcher.topk_pairs(batcher.stage(q), tj, n)
            ids.append(jax.device_get(i)[:nv])
            if with_scores:
                scores.append(jax.device_get(s)[:nv])
        return (
            np.concatenate(ids, axis=0),
            np.concatenate(scores, axis=0) if with_scores else None,
        )

    def recommend_for_all_users(
        self, num_items: int, with_scores: bool = False
    ):
        """Top-N item ids per user — one (n_users, r)x(r, n_items) MXU
        matmul + top_k (~ ALSModel.recommendForAllUsers).  Spark returns
        (item, rating) structs; ``with_scores=True`` returns the
        (ids, scores) pair (descending scores, the predicted
        preferences)."""
        ids, scores = self._top_k_scores(
            self.user_factors_, self.item_factors_, num_items,
            with_scores=with_scores,
        )
        return (ids, scores) if with_scores else ids

    def recommend_for_all_items(
        self, num_users: int, with_scores: bool = False
    ):
        """Top-N user ids per item (~ ALSModel.recommendForAllItems);
        ``with_scores`` as in recommend_for_all_users."""
        ids, scores = self._top_k_scores(
            self.item_factors_, self.user_factors_, num_users,
            with_scores=with_scores,
        )
        return (ids, scores) if with_scores else ids

    def _recommend_subset(self, query_ids, query_table, target_table,
                          n: int, with_scores: bool):
        """Shared subset recommender: row j of the result is the top-n
        for query_ids[j] (callers pass ids already validated/deduped)."""
        q = query_table[np.asarray(query_ids, np.int64)]
        ids, scores = self._top_k_scores(
            q, target_table, n, with_scores=with_scores
        )
        return (ids, scores) if with_scores else ids

    def recommend_for_users(self, user_ids, num_items: int,
                            with_scores: bool = False):
        """Top-N item ids for a SUBSET of users
        (~ ALSModel.recommendForUserSubset, reference
        spark-3.1.1/ml/recommendation/ALS.scala:379-403).  Row j is the
        recommendation list for ``user_ids[j]`` (ids must be in range;
        the compat layer applies Spark's distinct-and-join semantics)."""
        user_ids = np.asarray(user_ids, np.int64)
        n_u = self.user_factors_.shape[0]
        if len(user_ids) and (
            user_ids.min() < 0 or user_ids.max() >= n_u
        ):
            raise ValueError(
                f"user ids must be in [0, {n_u}); got range "
                f"[{user_ids.min()}, {user_ids.max()}]"
            )
        return self._recommend_subset(
            user_ids, self.user_factors_, self.item_factors_, num_items,
            with_scores,
        )

    def recommend_for_items(self, item_ids, num_users: int,
                            with_scores: bool = False):
        """Top-N user ids for a SUBSET of items
        (~ ALSModel.recommendForItemSubset, ALS.scala:405-429)."""
        item_ids = np.asarray(item_ids, np.int64)
        n_i = self.item_factors_.shape[0]
        if len(item_ids) and (
            item_ids.min() < 0 or item_ids.max() >= n_i
        ):
            raise ValueError(
                f"item ids must be in [0, {n_i}); got range "
                f"[{item_ids.min()}, {item_ids.max()}]"
            )
        return self._recommend_subset(
            item_ids, self.item_factors_, self.user_factors_, num_users,
            with_scores,
        )

    def fold_in_users(self, users, items, ratings, **kw) -> dict:
        """Incremental fold-in of new/changed USER rows against the
        frozen item table (online/foldin.py): one batched
        normal-equation solve per delta — the PR 9 half-update kernel,
        zero full refit — then an in-place serving re-pin.  ``users``/
        ``items``/``ratings`` are the touched users' FULL current
        rating rows (the standard fold-in contract; a partial row would
        silently solve against a truncated normal equation).  The user
        axis may GROW: ids beyond the current table extend it, with
        untouched new rows at the deterministic init.  Keyword
        arguments (reg/alpha/implicit/seed) default to the base fit's
        ``summary["params"]``.  Returns the commit record (rows solved,
        growth, new model version)."""
        from oap_mllib_tpu.online import foldin

        return foldin.fold_in(self, users, items, ratings,
                              side="user", **kw)

    def fold_in_items(self, users, items, ratings, **kw) -> dict:
        """Symmetric fold-in of new/changed ITEM rows against the
        frozen user table — see :meth:`fold_in_users`."""
        from oap_mllib_tpu.online import foldin

        return foldin.fold_in(self, users, items, ratings,
                              side="item", **kw)

    def save(self, path: str) -> None:
        """Atomic per-file writes, metadata last (data/io primitives) —
        the KMeansModel.save torn-write contract.  Sharded fits gather
        their factors first (a collective in multi-process worlds; the
        user_factors_ contract above)."""
        from oap_mllib_tpu.data import io as _io

        os.makedirs(path, exist_ok=True)
        _io.atomic_save_npy(
            os.path.join(path, "user_factors.npy"), self.user_factors_
        )
        _io.atomic_save_npy(
            os.path.join(path, "item_factors.npy"), self.item_factors_
        )
        _io.atomic_write_json(
            os.path.join(path, "metadata.json"),
            {"type": "ALSModel", "rank": int(self.rank),
             "user_shape": [int(v) for v in self.user_factors_.shape],
             "item_shape": [int(v) for v in self.item_factors_.shape],
             "version": 1},
        )

    @classmethod
    def load(cls, path: str) -> "ALSModel":
        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
        if meta.get("type") != "ALSModel":
            raise ValueError(f"not an ALSModel directory: {path}")
        uf = np.load(os.path.join(path, "user_factors.npy"))
        itf = np.load(os.path.join(path, "item_factors.npy"))
        for name, arr in (("user_factors.npy", uf), ("item_factors.npy", itf)):
            expect = meta.get(
                name.replace("_factors.npy", "_shape"),
                [None, meta["rank"]],
            )
            if arr.ndim != 2 or int(arr.shape[1]) != int(expect[1]) or (
                    expect[0] is not None
                    and int(arr.shape[0]) != int(expect[0])):
                raise ValueError(
                    f"{os.path.join(path, name)}: factors have shape "
                    f"{tuple(arr.shape)}, metadata expects {tuple(expect)} "
                    "— the model directory is torn or mixed from two saves"
                )
        return cls(uf, itf)


def _grouped_ok_single(kernel: str, users, items, n_users: int,
                       n_items: int) -> bool:
    """Grouped-vs-COO decision for the single-device layouts — ONE
    definition shared by the in-memory and streamed entries so the two
    paths can never route the same data to different kernels."""
    if kernel != "auto":
        return kernel == "grouped"
    padded_total = als_ops.grouped_padded_edges(
        users, n_users
    ) + als_ops.grouped_padded_edges(items, n_items)
    return padded_total <= als_ops.GROUPED_MAX_BLOWUP * max(len(users), 1)


def _als_kernel_cfg() -> str:
    """Validated Config.als_kernel — every dispatch site (single-device AND
    block-parallel) goes through this so a typo can never silently fall
    back to the auto heuristic."""
    from oap_mllib_tpu.config import get_config

    kernel = get_config().als_kernel
    if kernel not in ("auto", "grouped", "coo"):
        raise ValueError(
            f"als_kernel must be auto|grouped|coo, got {kernel!r}"
        )
    return kernel


class ALS:
    """ALS estimator. Param parity with Spark ML ALS defaults:
    rank=10, max_iter=10, reg_param=0.1, implicit_prefs=False, alpha=1.0."""

    def __init__(
        self,
        rank: int = 10,
        max_iter: int = 10,
        reg_param: float = 0.1,
        implicit_prefs: bool = False,
        alpha: float = 1.0,
        seed: Optional[int] = None,
        nonnegative: bool = False,
        num_user_blocks: Optional[int] = None,
        num_item_blocks: Optional[int] = None,
    ):
        if rank < 1:
            raise ValueError("rank must be >= 1")
        if max_iter < 0:
            raise ValueError("max_iter must be >= 0")
        if reg_param < 0:
            raise ValueError("reg_param must be >= 0")
        if alpha < 0:
            raise ValueError("alpha must be >= 0")
        if num_user_blocks is not None and num_user_blocks < 1:
            raise ValueError("num_user_blocks must be >= 1")
        if num_item_blocks is not None and num_item_blocks < 1:
            raise ValueError("num_item_blocks must be >= 1")
        self.rank = rank
        self.max_iter = max_iter
        self.reg_param = reg_param
        self.implicit_prefs = implicit_prefs
        self.alpha = alpha
        # None = Config.seed (the OAP_MLLIB_TPU_SEED default for
        # estimators that do not set one — docs/configuration.md)
        from oap_mllib_tpu.config import get_config

        self.seed = get_config().seed if seed is None else seed
        self.nonnegative = nonnegative
        # Block-layout hints (Spark ALS numUserBlocks/numItemBlocks,
        # reference ALS.scala:154-169).  Here the user-block count is the
        # mesh data-axis size (one block per device); num_user_blocks CAPS
        # it in single-process worlds.  The item side follows
        # config.als_item_layout: "sharded" gives world item blocks (the
        # 2-D grid), "replicated" one; num_item_blocks is recorded in the
        # fit summary but the layout knob is the config field.
        self.num_user_blocks = num_user_blocks
        self.num_item_blocks = num_item_blocks

    def fit(
        self,
        users,
        items: Optional[np.ndarray] = None,
        ratings: Optional[np.ndarray] = None,
        n_users: Optional[int] = None,
        n_items: Optional[int] = None,
        init: Optional[tuple] = None,
    ) -> ALSModel:
        """Fit factors from (user, item, rating) triples — see
        :meth:`_fit_impl` for the full contract.  This public wrapper
        additionally stamps the fit hyperparameters into
        ``model.summary["params"]`` so the incremental paths
        (online/foldin.py) can default reg/alpha/implicit/seed to
        exactly what the base fit used instead of asking the caller to
        re-plumb them."""
        model = self._fit_impl(
            users, items, ratings, n_users, n_items, init
        )
        model.summary.setdefault("params", {
            "rank": int(self.rank),
            "reg": float(self.reg_param),
            "alpha": float(self.alpha),
            "implicit": bool(self.implicit_prefs),
            "seed": int(self.seed),
        })
        return model

    def _fit_impl(
        self,
        users,
        items: Optional[np.ndarray] = None,
        ratings: Optional[np.ndarray] = None,
        n_users: Optional[int] = None,
        n_items: Optional[int] = None,
        init: Optional[tuple] = None,
    ) -> ALSModel:
        """Fit factors from (user, item, rating) triples.

        Regularization follows Spark's ALS-WR convention (reference
        ALS.scala:1794-1795): lambda is scaled by each row's rating count
        — r>0 count for implicit (whose confidence weights also follow
        Spark: alpha*|r| in A, b only for r>0), all ratings for explicit.

        Multi-host: when ``jax.process_count() > 1`` the triples are this
        process's LOCAL shard (the per-rank partitions of the reference's
        shuffle, ALSDALImpl.scala:95-109); n_users/n_items are resolved
        globally via allgathered maxima when not passed.

        Out-of-core: ``users`` may instead be a width-3
        :class:`~oap_mllib_tpu.data.stream.ChunkSource` of (user, item,
        rating) rows (``items``/``ratings`` omitted) — the fit then keeps
        device memory bounded by O(chunk + factors + moments) instead of
        holding the full grouped edge layouts in HBM (the K-Means/PCA
        streaming axis, extended to the hardest estimator;
        ops/als_stream.py).  Ids ride f64 chunks exactly (<= 2^53).
        """
        from oap_mllib_tpu.data.stream import ChunkSource

        if isinstance(users, ChunkSource):
            if items is not None or ratings is not None:
                raise ValueError(
                    "pass EITHER a triples ChunkSource OR explicit "
                    "users/items/ratings arrays"
                )
            return self._fit_source(users, n_users, n_items, init)
        if items is None or ratings is None:
            raise TypeError("fit needs items and ratings arrays")
        users, items, ratings, n_users, n_items = self._validate_resolve(
            users, items, ratings, n_users, n_items
        )

        # nonnegative uses the NNLS fallback path (the reference likewise
        # accelerates only the unconstrained implicit solver, ALS.scala:925)
        accelerated = should_accelerate(
            "ALS", guard_ok=not self.nonnegative, reason="nonnegative=True"
        )
        if init is not None:
            x0, y0 = np.array(init[0], np.float32), np.array(init[1], np.float32)
        else:
            # deferred: the block-parallel path inits its user blocks
            # per-process (counter-based init_factors_rows) so no host
            # ever materializes (n_users, rank)
            x0 = y0 = None

        if not accelerated:
            return self._fit_fallback_np(
                users, items, ratings, n_users, n_items, x0, y0
            )

        # accelerated path (~ ALSDALImpl.train, ALSDALImpl.scala:58)
        import jax

        from oap_mllib_tpu.parallel.mesh import get_mesh

        from oap_mllib_tpu.ops.als_block import als_item_layout_cfg
        from oap_mllib_tpu.utils import resilience

        als_item_layout_cfg()  # typo'd layout raises on every path
        mesh = get_mesh()
        world = mesh.shape[mesh.axis_names[0]]
        if (
            self.num_user_blocks is not None
            and jax.process_count() == 1
            and self.num_user_blocks < world
        ):
            # honor the numUserBlocks cap: fewer user blocks = a smaller
            # DATA axis (one block per data-axis slot), so the device
            # budget is blocks x model_parallel.  Multi-process worlds keep
            # one block per global device — restricting the device set
            # there would strand processes.
            mp = mesh.shape[mesh.axis_names[1]] if len(mesh.axis_names) > 1 else 1
            mesh = get_mesh(n_devices=self.num_user_blocks * mp)
            world = mesh.shape[mesh.axis_names[0]]
        # degradation ladder (utils/resilience.py): transient faults
        # retry the fit; the single-device grouped path maps the OOM
        # rung to the streamed (bounded-HBM) kernels; the final rung is
        # the same NumPy path the static gate falls back to
        stats = resilience.ResilienceStats()

        def fallback():
            return self._fit_fallback_np(
                users, items, ratings, n_users, n_items, x0, y0
            )

        from oap_mllib_tpu.utils import membudget

        multi = world > 1 or jax.process_count() > 1
        if multi:
            # memory-budget route plan (utils/membudget.py); the
            # single-device fit makes its own once it has counted the
            # padded edges (_fit_single_device)
            plan = membudget.plan_als(
                len(users), n_users, n_items, self.rank, world=world,
            )
            # distributed 2-D block layout for BOTH modes: ratings shuffled
            # by user block, X block-sharded, Y replicated (~ the
            # reference's full cShuffleData + 4-step pipeline, survey §3.3;
            # round 1 left explicit ALS on the unsharded global program)
            def attempt(degraded):
                timings = Timings("als.fit")
                cache_before = progcache.stats()
                tune_before = autotune.mark()
                model = self._fit_block_parallel(
                    users, items, ratings, n_users, n_items, x0, y0, mesh,
                    timings,
                )
                model.summary["progcache"] = progcache.delta(cache_before)
                model.summary["tuning"] = autotune.delta(tune_before)
                return model

            model = resilience.resilient_fit(
                "ALS", attempt, fallback, stats=stats
            )
            resilience.merge_stats(model.summary, stats)
            membudget.record_plan(model.summary, plan)
            telemetry.finalize_fit(model.summary)
            return model

        def attempt(degraded):
            return self._fit_single_device(
                users, items, ratings, n_users, n_items, x0, y0, degraded,
            )

        model = resilience.resilient_fit("ALS", attempt, fallback, stats=stats)
        resilience.merge_stats(model.summary, stats)
        telemetry.finalize_fit(model.summary)
        return model

    def _fit_fallback_np(self, users, items, ratings, n_users, n_items,
                         x0, y0) -> ALSModel:
        """The CPU/NumPy reference path — both the static fallback
        (failed dispatch predicate) and the resilience ladder's final
        rung reach the fit through here."""
        timings = Timings("als.fit")
        if x0 is None:
            x0 = als_np.init_factors(n_users, self.rank, self.seed)
            y0 = als_np.init_factors(n_items, self.rank, self.seed + 1)
        if self.nonnegative:
            # the nonnegative contract must hold even at max_iter=0 or
            # with a user-supplied signed init
            x0, y0 = np.abs(x0), np.abs(y0)
        with phase_timer(timings, "als_np"):
            x, y = als_np.als_np(
                users, items, ratings, n_users, n_items, self.rank,
                self.max_iter, self.reg_param, self.alpha,
                self.implicit_prefs, self.seed, init=(x0, y0),
                nonnegative=self.nonnegative,
            )
        return ALSModel(
            x, y,
            {"timings": timings, "accelerated": False,
             "item_layout": "replicated",
             **self._block_summary(1)},
        )

    # the id-space axes may GROW across restores (utils/checkpoint.py
    # growable axes): yesterday's checkpoint warm-starts today's fit
    # over a larger user/item universe — old rows restore bit-identical,
    # the grown tail initializes deterministically (_fill_grown)
    _GROWABLE = ("n_users", "n_items")

    def _ckpt_signature(self, n_users: int, n_items: int) -> dict:
        """Checkpoint identity (utils/checkpoint.py): the solver params
        and id-space shape.  World size, block layout, kernel choice,
        chunking, and precision policy are deliberately absent — every
        one of them may change across a preemption and the factor
        iterates remain valid state.  ``n_users``/``n_items`` are
        declared growable (``_GROWABLE``), so a restore accepts a
        manifest with a smaller id space (shape-prefix match) and
        records the growth in ``summary.checkpoint["grown"]``."""
        return {
            "rank": self.rank, "implicit": bool(self.implicit_prefs),
            "reg": float(self.reg_param), "alpha": float(self.alpha),
            "seed": int(self.seed), "n_users": int(n_users),
            "n_items": int(n_items),
        }

    def _fill_grown(self, grown: dict, x=None, y=None):
        """Initialize the GROWN tail of restored factor tables: rows
        [old, new) of a grown axis carry no checkpointed state (they
        restore zero-filled), so they get the deterministic init —
        ``als_np.init_factors_rows`` is position-addressable, making the
        filled rows bit-identical to what a from-scratch fit of the
        grown universe would have started those ids at."""
        if x is not None and "n_users" in grown:
            lo, hi = grown["n_users"]
            x = np.asarray(x, np.float32)
            x[lo:hi] = als_np.init_factors_rows(
                lo, hi, self.rank, self.seed
            )
        if y is not None and "n_items" in grown:
            lo, hi = grown["n_items"]
            y = np.asarray(y, np.float32)
            y[lo:hi] = als_np.init_factors_rows(
                lo, hi, self.rank, self.seed + 1
            )
        return x, y

    def _run_segmented(self, ckpt, x0, y0, run_iters, n_users, n_items):
        """Checkpoint-armed in-memory ALS: run the compiled scan in
        ``checkpoint_interval``-sized segments with a full-factor
        checkpoint between them.  The scan body is a pure function per
        iteration, so segmentation is bit-identical to the single
        compiled loop; ``run_iters(x, y, iters)`` runs one segment."""
        resume = ckpt.restore()
        done = 0
        x, y = x0, y0
        if resume.found:
            # either storage form — a block world's sharded checkpoint
            # restores onto this single-device fit too
            x = ckpt_mod.factors_from_result(resume, "x", n_users)
            y = ckpt_mod.factors_from_result(resume, "y", n_items)
            if resume.grown:
                x, y = self._fill_grown(resume.grown, x, y)
            done = min(int(resume.step), self.max_iter)
            if "x" not in resume.arrays:
                ckpt.mark_resharded()  # sharded state -> one device
        while done < self.max_iter:
            seg = min(ckpt.interval, self.max_iter - done)
            x, y = run_iters(x, y, seg)
            done += seg
            ckpt.maybe_write(
                done, {"x": np.asarray(x), "y": np.asarray(y)}, force=True,
            )
        return x, y

    def _fit_single_device(self, users, items, ratings, n_users, n_items,
                           x0, y0, degraded=0) -> ALSModel:
        """The single-device accelerated fit (grouped or COO layouts).
        ``degraded`` is the ladder's OOM rung level: the grouped path
        re-runs through the streamed kernels (ops/als_stream.py) at
        halved upload blocks — host-resident edges, O(chunk + factors +
        moments) HBM — which is exactly the memory-shedding retry a
        device OOM calls for; the COO path has no equivalent knob and
        re-runs unchanged (a persistent OOM then falls through to the
        NumPy rung).  The route plan (utils/membudget.py) is made here,
        from the padded edges the grouped build's counting pass has
        just counted, not from a constant: routed "streamed" (the HBM
        budget rejected the resident grouped layouts) the fit runs the
        same streamed kernels from the start — the budget-driven twin
        of the OOM rung, decided BEFORE the device ever faults.

        Spans of ``table_convert`` (docs/observability.md): ``host_copy``
        (ids or scores that did not come as int32 / float32 C arrays are
        copied once; ``attrs["copied_bytes"]``, 0 for the arrays Spark
        holds), ``group_edges`` (the counting pass, the guard, the plan
        and both grouped builds, over ``attrs["threads"]`` host
        threads), ``upload`` (data/table.upload_arrays)."""
        from oap_mllib_tpu.utils import membudget

        timings = Timings("als.fit")
        cache_before = progcache.stats()
        tune_before = autotune.mark()
        # compute-precision policy (utils/precision.py), resolved per
        # attempt so the ladder's f32-degradation scope applies on retry
        pol = psn.resolve("als")
        if x0 is None:
            x0 = als_np.init_factors(n_users, self.rank, self.seed)
            y0 = als_np.init_factors(n_items, self.rank, self.seed + 1)
        with phase_timer(timings, "table_convert"):
            # grouped-edge layout, one copy per update direction (the
            # reference's per-rank CSR + transposed CSR, ALSDALImpl.scala
            # :184-230 / .cpp:209-213, rebuilt for batched MXU matmuls —
            # see als_ops grouped-path notes); edge indices are static
            # across iterations so the sort/pad runs once per fit.  The
            # blowup guard runs on the counts BEFORE any (G, P) layout is
            # materialized (adaptive group sizing keeps typical data under
            # 2x; extreme long-tail degree splits would pad up to 8x nnz,
            # so a "coo" decision must not pay for the build).
            nnz = len(users)
            kernel = _als_kernel_cfg()
            if max(n_users, n_items) > np.iinfo(np.int32).max:
                raise ValueError(
                    "the edge layouts hold ids as int32: n_users and "
                    f"n_items must fit, got {n_users} and {n_items}"
                )
            with spans.child("host_copy") as span:
                handed = (users, items, ratings)
                users, items = (
                    np.ascontiguousarray(a, dtype=np.int32)
                    for a in (users, items)
                )
                ratings = np.ascontiguousarray(ratings, dtype=np.float32)
                span.attrs["copied_bytes"] = sum(
                    new.nbytes for new, old in
                    zip((users, items, ratings), handed) if new is not old
                )
            grouped_ok = kernel == "grouped"
            plan_kw = {}
            # (destinations, sources, their count) of the user side, the
            # item side
            sides = ((users, items, n_users), (items, users, n_items))
            # the program that gathers the grouped moments' factor rows:
            # one decision a fit, on the larger table
            gather = als_ops.resolve_gather_kernel(
                max(n_users, n_items), self.rank, x0.dtype
            )
            if kernel != "coo":
                with spans.child("group_edges") as span:
                    threads = als_ops.build_threads()
                    counts = [
                        als_ops.count_edges(dst, n_dst, threads)
                        for dst, _, n_dst in sides
                    ]
                    # the widths follow the counted degrees, within what
                    # the plan can hold resident (layouts + sheet)
                    sizes = als_ops.group_sizes_for(
                        counts, self.rank,
                        membudget.als_grouped_room(
                            n_users, n_items, self.rank
                        ),
                        gather,
                    )
                    padded = [
                        als_ops.padded_edges(c, p) for c, p in zip(counts, sizes)
                    ]
                    grouped_ok = grouped_ok or (
                        sum(padded) <= als_ops.GROUPED_MAX_BLOWUP * max(nnz, 1)
                    )
                    buckets = [
                        als_ops.group_bucket(tot // p)
                        for tot, p in zip(padded, sizes)
                    ]
                    if grouped_ok:
                        # what will be resident: the bucketed layouts
                        plan_kw["grouped"] = list(zip(buckets, sizes))
            plan = membudget.plan_als(
                nnz, n_users, n_items, self.rank, world=1, **plan_kw
            )
            if (plan.route == membudget.ROUTE_DST_BLOCKED
                    and min(sizes) < als_ops.DST_LEAST_GROUP):
                # that route multiplies a group as one tile of whole
                # lanes: it takes the cheapest widths of whole lanes
                with spans.child("group_edges"):
                    sizes = als_ops.group_sizes_for(
                        counts, self.rank, None, gather,
                        least=als_ops.DST_LEAST_GROUP,
                    )
                    padded = [
                        als_ops.padded_edges(c, p)
                        for c, p in zip(counts, sizes)
                    ]
                    buckets = [
                        als_ops.group_bucket(tot // p)
                        for tot, p in zip(padded, sizes)
                    ]
                plan = membudget.plan_als(
                    nnz, n_users, n_items, self.rank, world=1,
                    grouped=list(zip(buckets, sizes)),
                )
            planned_streamed = plan.route == membudget.ROUTE_STREAMED
            if planned_streamed and not grouped_ok:
                # the planner routed streamed but the degree
                # distribution forces COO (streaming is grouped-only) —
                # a scale downgrade that must never be silent: strict
                # raises BudgetError here, auto warns + records
                plan.downgrade(
                    membudget.ROUTE_IN_MEMORY,
                    "grouped guard rejected the degree distribution "
                    "(COO streaming unsupported)",
                )
                planned_streamed = False
            stream_route = bool(degraded) or planned_streamed
            # the resident layouts, solved a block of destinations at a
            # time: where the grouped route's sheet does not fit
            dst_route = (
                grouped_ok and not stream_route
                and plan.route == membudget.ROUTE_DST_BLOCKED
            )
            if grouped_ok:
                with spans.child("group_edges") as span:
                    by_user, by_item = (
                        als_ops.build_grouped_edges(
                            dst, src, ratings, n_dst, p, counts=c,
                            # the streamed kernels chunk the layouts
                            # themselves: no bucket for them
                            groups=0 if stream_route else g,
                            threads=threads,
                        )
                        for (dst, src, n_dst), c, p, g in
                        zip(sides, counts, sizes, buckets)
                    )
                    span.attrs.update(
                        ratings=nnz, padded_edges_user=padded[0],
                        padded_edges_item=padded[1], group_size=sizes,
                        # what the mean degree alone would have chosen
                        group_size_by_mean=[
                            als_ops.auto_group_size(nnz, n_dst)
                            for _, _, n_dst in sides
                        ],
                        groups_user=by_user[3].shape[0],
                        groups_item=by_item[3].shape[0],
                        # of the wider side; the streamed kernels and
                        # the destination-blocked route hold none
                        sheet_bytes=0 if stream_route or dst_route
                        else membudget.als_sheet_bytes(
                            max(buckets), self.rank
                        ),
                        threads=int(counts[0].shape[0]),
                    )
                if not stream_route:
                    # the streamed route keeps the layouts HOST-resident
                    # for the streamed kernels instead of uploading both
                    from oap_mllib_tpu.data.table import upload_arrays

                    dev = tuple(upload_arrays(
                        [*by_user, *by_item],
                        jax.sharding.SingleDeviceSharding(
                            jax.local_devices()[0]
                        ),
                        # a layout's G: its group_dst's length; the rest
                        # of its bucket's pad groups are zeros there
                        rows=[g[3].shape[0] for g in (by_user, by_item)
                              for _ in g],
                    ))
            else:
                # COO nnz pads to a shape bucket (data/bucketing.py,
                # anchored at the 2048 edge-chunk multiple): the COO
                # programs are keyed on padded nnz, so refits of a
                # growing ratings set within one bucket reuse the
                # compiled loop; padding edges carry valid=0
                from oap_mllib_tpu.data.bucketing import bucket_rows

                pad = bucket_rows(nnz, 2048) - nnz
                u = jnp.asarray(np.pad(users, (0, pad)).astype(np.int32))
                i = jnp.asarray(np.pad(items, (0, pad)).astype(np.int32))
                c = jnp.asarray(np.pad(ratings, (0, pad)))
                valid = jnp.asarray(np.pad(np.ones(nnz, np.float32), (0, pad)))
        from oap_mllib_tpu.utils.profiling import maybe_trace

        ckpt = ckpt_mod.maybe_open(
            "als", self._ckpt_signature(n_users, n_items), timings=timings,
            growable=self._GROWABLE,
        )
        solve_kernel = als_ops.resolve_solve_kernel(self.rank, x0.dtype)
        with timings.span("als_iterations") as span, maybe_trace():
            span.attrs.update(
                iterations=int(self.max_iter), solve_kernel=solve_kernel,
                rank=int(self.rank), implicit=bool(self.implicit_prefs),
            )
            if grouped_ok and not dst_route:
                from oap_mllib_tpu.ops.pallas import als_gather

                # the walk's packed tables, the user side's (items) first
                span.attrs.update(
                    gather_kernel=gather,
                    gather_table_bytes=[
                        als_gather.table_bytes(n_src, self.rank)
                        if gather.startswith("pallas") else 0
                        for n_src in (n_items, n_users)
                    ],
                )
            if dst_route:
                from oap_mllib_tpu.ops.pallas import als_dst

                moments, solve = als_ops.resolve_dst_kernels()
                blocks = [
                    als_ops.dst_blocks(n, plan.dst_block)[1]
                    for n in (n_users, n_items)
                ]
                # the slots a fit's chunks hold, and the rows its moments
                # read: the XLA twin gathers every slot, the kernel copies
                # every slot too and, at each chunk's last grid step, the
                # step's rows once more
                walked = fetched = 0
                for g, pad, p, n in zip((by_user, by_item), padded, sizes,
                                        (n_users, n_items)):
                    chunk = als_ops.dst_chunk_groups(p, self.rank)
                    chunks = int(self.max_iter) * als_ops.dst_walked_chunks(
                        g[3], pad // p, n, plan.dst_block, chunk
                    )
                    gc = min(chunk, g[3].shape[0])
                    walked += chunks * gc * p
                    fetched += chunks * (gc + als_dst.STEP_GROUPS) * p
                dma = moments.startswith("pallas")
                span.attrs.update(
                    route=plan.route, dst_block=int(plan.dst_block),
                    blocks_user=blocks[0], blocks_item=blocks[1],
                    r_pad=als_dst.r_pad(self.rank),
                    gather="dma" if dma else "rows",
                    rows_fetched=fetched if dma else walked,
                    slots_walked=walked, moments=moments, solve=solve,
                )

                def run_iters(xa, ya, iters):
                    return als_ops.als_run_dst_blocked(
                        *dev, jnp.asarray(xa), jnp.asarray(ya),
                        n_users, n_items, iters, self.reg_param,
                        self.alpha, self.implicit_prefs,
                        dst_block=plan.dst_block, timings=timings,
                        policy=pol.name, moments=moments, solve=solve,
                    )

                if ckpt is None:
                    x, y = run_iters(x0, y0, self.max_iter)
                else:
                    x, y = self._run_segmented(
                        ckpt, x0, y0, run_iters, n_users, n_items
                    )
            elif grouped_ok and stream_route:
                from oap_mllib_tpu.ops import als_stream

                x, y = als_stream.als_run_streamed(
                    by_user, by_item, x0, y0, n_users, n_items,
                    self.max_iter, self.reg_param, self.alpha,
                    self.implicit_prefs, timings=timings,
                    degraded=bool(degraded), policy=pol.name,
                    checkpoint=ckpt, grown_fill=self._fill_grown,
                )
            elif grouped_ok:
                def run_iters(xa, ya, iters):
                    return als_ops.als_run_grouped(
                        *dev, jnp.asarray(xa), jnp.asarray(ya),
                        n_users, n_items, iters, self.reg_param,
                        self.alpha, self.implicit_prefs, timings=timings,
                        policy=pol.name, solve_kernel=solve_kernel,
                        gather_kernel=gather,
                    )

                if ckpt is None:
                    x, y = run_iters(x0, y0, self.max_iter)
                else:
                    x, y = self._run_segmented(
                        ckpt, x0, y0, run_iters, n_users, n_items
                    )
            elif self.implicit_prefs:
                def run_iters(xa, ya, iters):
                    return als_ops.als_implicit_run(
                        u, i, c, valid, jnp.asarray(xa), jnp.asarray(ya),
                        n_users, n_items, iters, self.reg_param,
                        self.alpha, timings=timings, policy=pol.name,
                    )

                if ckpt is None:
                    x, y = run_iters(x0, y0, self.max_iter)
                else:
                    x, y = self._run_segmented(
                        ckpt, x0, y0, run_iters, n_users, n_items
                    )
            else:
                def run_iters(xa, ya, iters):
                    return als_ops.als_explicit_run(
                        u, i, c, valid, jnp.asarray(xa), jnp.asarray(ya),
                        n_users, n_items, iters, self.reg_param,
                        timings=timings, policy=pol.name,
                    )

                if ckpt is None:
                    x, y = run_iters(x0, y0, self.max_iter)
                else:
                    x, y = self._run_segmented(
                        ckpt, x0, y0, run_iters, n_users, n_items
                    )
            # the fit ends when both factor tables are back on the host
            x, y = spans.fetch(lambda: (np.asarray(x), np.asarray(y)))
        timings.root.attrs["kernel"] = (
            "dst_blocked" if dst_route else "grouped" if grouped_ok else "coo"
        )
        summary = {
            "timings": timings, "accelerated": True,
            "als_kernel": timings.root.attrs["kernel"],
            "item_layout": "replicated",
            "progcache": progcache.delta(cache_before),
            "tuning": autotune.delta(tune_before),
            **self._block_summary(1),
        }
        if stream_route and grouped_ok:
            # the OOM rung or the budget plan ran the streamed kernels
            summary["streamed"] = True
        psn.record(summary, timings, pol)
        membudget.record_plan(summary, plan)
        if ckpt is not None:
            ckpt.record(summary)
        return ALSModel(x, y, summary)

    @staticmethod
    def _validate_resolve(users, items, ratings, n_users, n_items):
        """Shared triple validation + id-space resolution (array and
        streamed entries).  Multi-process: global maxima by allgather
        (the reference's RDD max jobs, ALSDALImpl.scala:62-70).  Ids
        handed over as int32 ndarrays — what Spark ML ALS holds — and
        float32 ratings pass as they are, uncopied; anything else is
        made int64 / float32 here."""
        users, items = (
            a if isinstance(a, np.ndarray) and a.dtype == np.int32
            else np.asarray(a, dtype=np.int64)
            for a in (users, items)
        )
        ratings = np.asarray(ratings, dtype=np.float32)
        if not (len(users) == len(items) == len(ratings)):
            raise ValueError("users/items/ratings must have equal length")
        if len(users) == 0:
            raise ValueError("empty ratings")
        if users.min() < 0 or items.min() < 0:
            raise ValueError("ids must be non-negative")
        import jax as _jax

        if _jax.process_count() > 1:
            from jax.experimental import multihost_utils

            maxes = np.asarray(
                multihost_utils.process_allgather(
                    np.asarray([users.max(), items.max()], np.int64)
                )
            ).reshape(-1, 2)
            if n_users is None:
                n_users = int(maxes[:, 0].max()) + 1
            if n_items is None:
                n_items = int(maxes[:, 1].max()) + 1
        if n_users is None:
            n_users = int(users.max()) + 1
        elif int(users.max()) >= n_users:
            raise ValueError(
                f"user id {int(users.max())} out of range for n_users={n_users}"
            )
        if n_items is None:
            n_items = int(items.max()) + 1
        elif int(items.max()) >= n_items:
            raise ValueError(
                f"item id {int(items.max())} out of range for n_items={n_items}"
            )
        return users, items, ratings, n_users, n_items

    def _fit_source(self, source, n_users, n_items, init) -> ALSModel:
        """Out-of-core fit from a width-3 (user, item, rating) ChunkSource
        (ops/als_stream.py).  The triples are ingested to host arrays —
        host RAM is O(nnz), like the reference's executor partitions
        (OneDAL.scala:92-166) — and the STREAMED property is device
        memory: only one budget-bounded chunk of the grouped edge layouts
        is resident per step, with factors staying on device.

        Multi-device / multi-process worlds COMPOSE streaming with the
        mesh (ops/als_block_stream.py): each rank keeps only its block's
        grouped layouts in host RAM and streams them through its device,
        with the block path's collective structure unchanged — per-device
        HBM stays O(chunk + factors + moments) while nnz scales with
        aggregate host RAM.

        Falls back to the standard in-memory fit only when the streamed
        path does not apply: fallback/nonnegative dispatch, or a
        long-tail degree distribution the grouped guard rejects (COO
        streaming would need a lane-padded (n_dst, r, r) resident
        accumulator — the flat-moment trick is grouped-only)."""
        import jax

        from oap_mllib_tpu.utils import resilience

        if source.n_features != 3:
            raise ValueError(
                "ALS source must have width 3 (user, item, rating); "
                f"got {source.n_features}"
            )
        stats = resilience.ResilienceStats()

        def ingest():
            us, its, rs = [], [], []
            for chunk, n_valid in source:
                us.append(np.asarray(chunk[:n_valid, 0], np.int64))
                its.append(np.asarray(chunk[:n_valid, 1], np.int64))
                rs.append(np.asarray(chunk[:n_valid, 2], np.float32))
            return (
                np.concatenate(us) if us else np.zeros((0,), np.int64),
                np.concatenate(its) if its else np.zeros((0,), np.int64),
                np.concatenate(rs) if rs else np.zeros((0,), np.float32),
            )

        # the ingestion pass sits BEFORE any fit ladder, so transient
        # source faults (the stream.read site) get their own retry tier
        # here; its counters merge into the same per-fit stats
        users, items, ratings = resilience.run_with_retry(
            ingest, stats=stats, site="ALS.ingest"
        )

        accelerated = should_accelerate(
            "ALS", guard_ok=not self.nonnegative, reason="nonnegative=True"
        )
        if not accelerated:
            return self.fit(
                users, items, ratings, n_users=n_users, n_items=n_items,
                init=init,
            )

        from oap_mllib_tpu.parallel.mesh import get_mesh
        from oap_mllib_tpu.ops.als_block import als_item_layout_cfg

        als_item_layout_cfg()  # typo'd layout raises on every path
        mesh = get_mesh()
        world = mesh.shape[mesh.axis_names[0]]
        if (
            self.num_user_blocks is not None
            and jax.process_count() == 1
            and self.num_user_blocks < world
        ):
            # same numUserBlocks cap as the in-memory fit (see fit)
            mp = (
                mesh.shape[mesh.axis_names[1]]
                if len(mesh.axis_names) > 1 else 1
            )
            mesh = get_mesh(n_devices=self.num_user_blocks * mp)
            world = mesh.shape[mesh.axis_names[0]]
        users, items, ratings, n_users, n_items = self._validate_resolve(
            users, items, ratings, n_users, n_items
        )
        kernel = _als_kernel_cfg()
        from oap_mllib_tpu.utils import membudget

        multi = world > 1 or jax.process_count() > 1
        # route plan for the SOURCE entry: the natural route is streamed
        # (streamed-block on a mesh) — any materialization back to
        # in-memory layouts below is a recorded, loud scale downgrade
        # (BudgetError under strict), never a silent fallback
        plan = membudget.plan_als(
            len(users), n_users, n_items, self.rank,
            world=world if multi else 1, source_backing=source.backing,
        )
        if multi:
            # out-of-core COMPOSED with the mesh: per-rank streamed
            # grouped accumulation inside the block layout
            # (ops/als_block_stream.py) — a multi-device world no longer
            # silently falls back to fully-resident device layouts.
            # Ladder: transient retries + the NumPy final rung (the
            # block chunking has no halved-chunk knob; single-process
            # worlds only — resilient_fit bypasses itself multi-process)
            model = resilience.resilient_fit(
                "ALS",
                lambda degraded: self._fit_source_block(
                    users, items, ratings, n_users, n_items, init, mesh,
                    plan=plan,
                ),
                lambda: self._fit_fallback_np(
                    users, items, ratings, n_users, n_items,
                    None if init is None else np.array(init[0], np.float32),
                    None if init is None else np.array(init[1], np.float32),
                ),
                stats=stats,
            )
            resilience.merge_stats(model.summary, stats)
            membudget.record_plan(model.summary, plan)
            telemetry.finalize_fit(model.summary)
            return model
        if not _grouped_ok_single(kernel, users, items, n_users, n_items):
            # in-memory COO fallback (the guard re-runs inside fit — an
            # O(nnz) native bincount, cheap next to the fit itself).
            # This IS a scale downgrade of a source fit: record it
            # loudly (strict raises) — the planner contract
            plan.downgrade(
                membudget.ROUTE_IN_MEMORY,
                "grouped guard rejected the degree distribution "
                "(COO streaming unsupported)",
            )
            model = self.fit(
                users, items, ratings, n_users=n_users, n_items=n_items,
                init=init,
            )
            # the source-level plan (with its downgrade trail) replaces
            # the array entry's own record on the summary
            membudget.record_plan(model.summary, plan)
            return model

        from oap_mllib_tpu.ops import als_stream

        if init is not None:
            x0 = np.array(init[0], np.float32)
            y0 = np.array(init[1], np.float32)
        else:
            x0 = als_np.init_factors(n_users, self.rank, self.seed)
            y0 = als_np.init_factors(n_items, self.rank, self.seed + 1)

        def attempt(degraded):
            timings = Timings("als.fit")
            cache_before = progcache.stats()
            tune_before = autotune.mark()
            pol = psn.resolve("als")
            with phase_timer(timings, "table_convert"):
                by_user = als_ops.build_grouped_edges(
                    users, items, ratings, n_users
                )
                by_item = als_ops.build_grouped_edges(
                    items, users, ratings, n_items
                )
            from oap_mllib_tpu.utils.profiling import maybe_trace

            ckpt = ckpt_mod.maybe_open(
                "als", self._ckpt_signature(n_users, n_items),
                timings=timings, growable=self._GROWABLE,
            )
            with phase_timer(timings, "als_iterations"), maybe_trace():
                x, y = als_stream.als_run_streamed(
                    by_user, by_item, x0, y0, n_users, n_items,
                    self.max_iter, self.reg_param, self.alpha,
                    self.implicit_prefs, timings=timings,
                    degraded=degraded, policy=pol.name, checkpoint=ckpt,
                    grown_fill=self._fill_grown,
                )
            summary = {
                "timings": timings, "accelerated": True, "streamed": True,
                "als_kernel": "grouped", "item_layout": "replicated",
                "progcache": progcache.delta(cache_before),
                "tuning": autotune.delta(tune_before),
                **self._block_summary(1),
            }
            psn.record(summary, timings, pol)
            if ckpt is not None:
                ckpt.record(summary)
            return ALSModel(x, y, summary)

        model = resilience.resilient_fit(
            "ALS", attempt,
            lambda: self._fit_fallback_np(
                users, items, ratings, n_users, n_items, x0, y0
            ),
            stats=stats,
        )
        resilience.merge_stats(model.summary, stats)
        membudget.record_plan(model.summary, plan)
        telemetry.finalize_fit(model.summary)
        return model

    def _block_dispatch(self, users, items, n_users, n_items, world):
        """(item_sharded, use_grouped, sizes) — ONE decision point for
        both block fits (in-memory and streamed), so the layout choice,
        the grouped-vs-COO guard, and the group sizes the guard priced
        can never diverge between them.  ``sizes`` is the guard's
        (p_u, p_i, nnz_global) when it ran, else None (forced kernel)."""
        from oap_mllib_tpu.ops import als_block

        item_sharded = als_block.item_layout_sharded(
            n_items, self.rank, world, n_users
        )
        kernel = _als_kernel_cfg()
        sizes = None
        if kernel == "auto":
            guard_fn = (
                als_block.block_grouped_guard_2d
                if item_sharded
                else als_block.block_grouped_guard
            )
            use_grouped, sizes = guard_fn(
                users, items, n_users, n_items, world
            )
        else:
            use_grouped = kernel == "grouped"
        return item_sharded, use_grouped, sizes

    def _place_block_factors(self, mesh, offsets, per: int,
                             init_full: Optional[np.ndarray], seed: int):
        """Block-sharded (world*per, rank) factor init where each
        device's callback builds ONLY its block's rows — from the user
        init if given, else the counter-based position-addressable
        generator (bit-identical to the global init_factors rows; the
        per-rank seeding of the reference, ALSDALImpl.cpp:165-169).  No
        host materializes the full matrix."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from oap_mllib_tpu.config import get_config

        world = len(offsets) - 1
        sharding = NamedSharding(mesh, P(get_config().data_axis, None))

        def blk(idx):
            b = (idx[0].start or 0) // per
            lo, hi = int(offsets[b]), int(offsets[b + 1])
            out = np.zeros((per, self.rank), np.float32)
            if init_full is not None:
                out[: hi - lo] = init_full[lo:hi]
            else:
                out[: hi - lo] = als_np.init_factors_rows(
                    lo, hi, self.rank, seed
                )
            return out

        return jax.make_array_from_callback(
            (world * per, self.rank), sharding, blk
        )

    def _fit_source_block(
        self, users, items, ratings, n_users, n_items, init, mesh,
        plan=None,
    ) -> ALSModel:
        """Streamed fit composed with the mesh (ops/als_block_stream.py):
        host-resident per-rank grouped layouts, chunked uploads, the
        block path's psum / all_gather structure.  COO long-tail data
        falls back to the in-memory block fit (grouped-only streaming,
        see _fit_source notes) — recorded as a loud downgrade on the
        plan (BudgetError under strict), never silent."""
        import jax

        from oap_mllib_tpu.ops import als_block_stream
        from oap_mllib_tpu.utils import membudget

        world = mesh.shape[mesh.axis_names[0]]
        item_sharded, use_grouped, sizes = self._block_dispatch(
            users, items, n_users, n_items, world
        )
        if not use_grouped:
            if plan is not None:
                plan.downgrade(
                    membudget.ROUTE_IN_MEMORY,
                    "grouped guard rejected the degree distribution "
                    "(COO streaming unsupported)",
                )
            return self.fit(
                users, items, ratings, n_users=n_users, n_items=n_items,
                init=init,
            )
        timings = Timings("als.fit")
        cache_before = progcache.stats()
        tune_before = autotune.mark()
        pol = psn.resolve("als")
        x0 = None if init is None else np.array(init[0], np.float32)
        y0 = None if init is None else np.array(init[1], np.float32)
        # capability-weighted user blocks for the STREAMED layout too
        # (same planner + deadband as the in-memory fit below): a slow
        # rank streams and solves a smaller user block.  The 2-D
        # sharded-item layout keeps the uniform split — its identity
        # mapping requires it — and None (homogeneous worlds) keeps the
        # layout bit-identical.
        bal_offsets = None
        if not item_sharded:
            from oap_mllib_tpu.parallel import balance

            bal_offsets = balance.block_offsets(
                n_users, world,
                bytes_per_key=4 * (self.rank
                                   + (self.rank + 1) * (self.rank + 2)),
            )
        with phase_timer(timings, "table_convert"):
            lay = als_block_stream.prepare_streamed_block_layouts(
                users, items, ratings, n_users, n_items, mesh, self.rank,
                item_sharded=item_sharded, sizes=sizes,
                offsets=bal_offsets,
            )
            x0_dev = self._place_block_factors(
                mesh, lay.offsets_u, lay.upb, x0, self.seed
            )
            if item_sharded:
                y0_dev = self._place_block_factors(
                    mesh, lay.offsets_i, lay.ipb, y0, self.seed + 1
                )
            else:
                from jax.sharding import NamedSharding, PartitionSpec as P

                y0_host = (
                    y0 if y0 is not None
                    else als_np.init_factors(n_items, self.rank,
                                             self.seed + 1)
                )
                y0_dev = jax.make_array_from_callback(
                    (n_items, self.rank), NamedSharding(mesh, P()),
                    lambda idx: y0_host[idx],
                )
        from oap_mllib_tpu.utils.profiling import maybe_trace

        ckpt = ckpt_mod.maybe_open(
            "als", self._ckpt_signature(n_users, n_items), timings=timings,
            growable=self._GROWABLE,
        )
        with phase_timer(timings, "als_iterations"), maybe_trace():
            x_blocks, y = als_block_stream.als_block_run_streamed(
                lay, x0_dev, y0_dev, self.max_iter, self.reg_param,
                self.alpha, mesh, implicit=self.implicit_prefs,
                timings=timings, policy=pol.name, checkpoint=ckpt,
            )
            # oaplint: disable=stream-host-sync -- end-of-fit barrier so
            jax.block_until_ready((x_blocks, y))  # phase_timer sees walls
        summary = {
            "timings": timings, "accelerated": True, "streamed": True,
            "block_parallel": True, "sharded_factors": True,
            "als_kernel": "grouped",
            "item_layout": "sharded" if item_sharded else "replicated",
            "progcache": progcache.delta(cache_before),
            "tuning": autotune.delta(tune_before),
            **self._block_summary(world),
        }
        psn.record(summary, timings, pol)
        if ckpt is not None:
            ckpt.record(summary)
        if item_sharded:
            return ALSModel(
                None, None, summary,
                sharded_user=(x_blocks, np.asarray(lay.offsets_u), lay.upb),
                sharded_item=(y, np.asarray(lay.offsets_i), lay.ipb),
            )
        return ALSModel(
            None, np.asarray(y), summary,
            sharded_user=(x_blocks, np.asarray(lay.offsets_u), lay.upb),
        )

    def _run_block_segmented(self, ckpt, run_iters, x0_dev, y0_dev, mesh,
                             offsets, upb, ioffsets, ipb, item_sharded):
        """Checkpoint-armed block-parallel ALS (in-memory runners): the
        compiled runners execute in ``checkpoint_interval``-sized
        segments; between segments every rank writes ITS blocks' valid
        factor rows (global ids + values), and restore re-buckets
        whatever shards the relaunched world read onto the LIVE block
        layout through the collective resharding pass
        (parallel/shuffle.reshard_factor_rows) — the full table never
        materializes on one host.  ``run_iters(x, y, iters)`` runs one
        segment on device arrays in the runner's block forms."""
        from oap_mllib_tpu.parallel.shuffle import reshard_factor_rows
        from jax.sharding import NamedSharding, PartitionSpec as P

        layout = {
            "offsets_u": [int(v) for v in offsets],
            "upb": int(upb),
            "item_sharded": bool(item_sharded),
        }
        if item_sharded:
            layout["offsets_i"] = [int(v) for v in ioffsets]
            layout["ipb"] = int(ipb)
        resume = ckpt.restore()
        done = 0
        x, y = x0_dev, y0_dev
        if resume.found:
            done = min(int(resume.step), self.max_iter)
            nproc, rank = jax.process_count(), jax.process_index()
            ids_u, vals_u = ckpt_mod.sharded_rows_from_result(
                resume, "x", nproc, rank
            )
            x = reshard_factor_rows(ids_u, vals_u, mesh, offsets, upb)
            if item_sharded:
                ids_i, vals_i = ckpt_mod.sharded_rows_from_result(
                    resume, "y", nproc, rank
                )
                y = reshard_factor_rows(ids_i, vals_i, mesh, ioffsets, ipb)
            else:
                y_host = ckpt_mod.replicated_from_result(
                    resume, "y", int(y0_dev.shape[0]),
                )
                if resume.grown:
                    # grown item tail gets the deterministic init (the
                    # grown USER tail stays zero in the sharded x — its
                    # rows re-solve from y in the next half-iteration)
                    _, y_host = self._fill_grown(resume.grown, None, y_host)
                y = jax.make_array_from_callback(
                    y_host.shape, NamedSharding(mesh, P()),
                    lambda idx: y_host[idx],
                )
            if resume.layout != layout:
                ckpt.mark_resharded()
        while done < self.max_iter:
            seg = min(ckpt.interval, self.max_iter - done)
            x, y = run_iters(x, y, seg)
            done += seg
            sharded = {"x": ckpt_mod.local_factor_rows(x, offsets, upb)}
            arrays = {}
            if item_sharded:
                sharded["y"] = ckpt_mod.local_factor_rows(y, ioffsets, ipb)
            else:
                arrays["y"] = np.asarray(y)
            ckpt.maybe_write(
                done, arrays, sharded=sharded, layout=layout, force=True,
            )
        return x, y

    def _block_summary(self, effective_user_blocks: int) -> dict:
        """Requested vs effective block layout for the fit summary."""
        out = {"num_user_blocks": effective_user_blocks}
        if self.num_user_blocks is not None:
            out["num_user_blocks_requested"] = self.num_user_blocks
        if self.num_item_blocks is not None:
            out["num_item_blocks_requested"] = self.num_item_blocks
        return out

    def _fit_block_parallel(
        self, users, items, ratings, n_users, n_items, x0, y0, mesh, timings
    ) -> ALSModel:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from oap_mllib_tpu.config import get_config
        from oap_mllib_tpu.ops import als_block

        cfg = get_config()
        axis = cfg.data_axis
        world = mesh.shape[axis]
        pol = psn.resolve("als")
        # item-factor layout (replicated-Y vs the full 2-D grid) and the
        # pre-shuffle grouped-vs-COO guard — the shared decision point
        # (_block_dispatch): a COO decision pays neither the grouped
        # build nor the device->host pull of the shuffled blocks
        item_sharded, use_grouped, sizes = self._block_dispatch(
            users, items, n_users, n_items, world
        )
        # capability-weighted user blocks (parallel/balance.py, ISSUE
        # 15): on the replicated-item layout a slow rank gets a smaller
        # user block (offsets proportional to the gathered capability
        # weights, HBM-priced) — every consumer of (offsets, upb)
        # downstream is boundary-generic.  The 2-D sharded layout keeps
        # the uniform split: its all_gather indexing is the identity
        # mapping only uniform blocks provide.  Near-equal worlds return
        # None here (deadband), keeping homogeneous fits bit-identical.
        bal_offsets = None
        if not item_sharded:
            from oap_mllib_tpu.parallel import balance

            # per-key resident bytes: one f32 factor row (r) + the
            # per-key normal-equation moment block ((r+1)(r+2) flat)
            bal_offsets = balance.block_offsets(
                n_users, world,
                bytes_per_key=4 * (self.rank
                                   + (self.rank + 1) * (self.rank + 2)),
            )
        with phase_timer(timings, "ratings_shuffle"):
            u_loc, i_glob, conf, valid, offsets, upb = als_block.prepare_block_inputs(
                users, items, ratings, mesh, n_users, offsets=bal_offsets
            )
            item_shuffle = None
            if item_sharded:
                # second shuffle, by ITEM block: the transposed per-rank
                # table of the reference (ALSDALImpl.cpp:192-214) as a
                # role-swapped run of the same exchange
                i_loc, u_glob, conf_i, valid_i, ioffsets, ipb = (
                    als_block.prepare_block_inputs(
                        items, users, ratings, mesh, n_items
                    )
                )
                item_shuffle = (i_loc, u_glob, conf_i, valid_i)
            grouped = None
            if use_grouped:
                # scatter-free grouped-edge layouts per rank (the one-time
                # device->host pull of the shuffled blocks happens only on
                # this branch; see als_ops grouped notes)
                if item_sharded:
                    grouped = als_block.prepare_grouped_inputs_2d(
                        u_loc, i_glob, conf, valid,
                        i_loc, u_glob, conf_i, valid_i,
                        mesh, upb, ipb, sizes=sizes,
                    )
                else:
                    grouped = als_block.prepare_grouped_inputs(
                        u_loc, i_glob, conf, valid, mesh, upb, n_items,
                        sizes=sizes,
                    )
        with phase_timer(timings, "table_convert"):
            # block X init stays rank-local — no host materializes
            # (n_users, r); see _place_block_factors
            x0_dev = self._place_block_factors(
                mesh, offsets, upb, x0, self.seed
            )
            if item_sharded:
                # Y block-sharded like X; real rows from the SAME
                # position-addressable generator the replicated path
                # seeds (bit-identical rows), padding zero — the zeros
                # keep the psummed block Grams exact
                y0_dev = self._place_block_factors(
                    mesh, ioffsets, ipb, y0, self.seed + 1
                )
            else:
                y0_host = (
                    y0 if y0 is not None
                    else als_np.init_factors(n_items, self.rank, self.seed + 1)
                )
                y0_dev = jax.make_array_from_callback(
                    (n_items, self.rank), NamedSharding(mesh, P()),
                    lambda idx: y0_host[idx],
                )
        from oap_mllib_tpu.utils.profiling import maybe_trace

        ckpt = ckpt_mod.maybe_open(
            "als", self._ckpt_signature(n_users, n_items), timings=timings,
            growable=self._GROWABLE,
        )
        with phase_timer(timings, "als_iterations"), maybe_trace():
            if item_sharded:
                if grouped is not None:
                    def run_iters(xa, ya, iters):
                        return als_block.als_block_run_grouped_2d(
                            grouped, xa, ya,
                            iters, self.reg_param, self.alpha, mesh,
                            implicit=self.implicit_prefs, policy=pol.name,
                        )
                else:
                    def run_iters(xa, ya, iters):
                        return als_block.als_block_run_2d(
                            u_loc, i_glob, conf, valid, *item_shuffle,
                            xa, ya,
                            iters, self.reg_param, self.alpha, mesh,
                            implicit=self.implicit_prefs, policy=pol.name,
                        )
            elif grouped is not None:
                def run_iters(xa, ya, iters):
                    return als_block.als_block_run_grouped(
                        grouped, xa, ya,
                        iters, self.reg_param, self.alpha, mesh,
                        implicit=self.implicit_prefs, policy=pol.name,
                    )
            else:
                def run_iters(xa, ya, iters):
                    return als_block.als_block_run(
                        u_loc, i_glob, conf, valid, xa, ya,
                        iters, self.reg_param, self.alpha, mesh,
                        implicit=self.implicit_prefs, policy=pol.name,
                    )

            if ckpt is None:
                x_blocks, y = run_iters(x0_dev, y0_dev, self.max_iter)
            else:
                x_blocks, y = self._run_block_segmented(
                    ckpt, run_iters, x0_dev, y0_dev, mesh,
                    offsets, upb,
                    ioffsets if item_sharded else None,
                    ipb if item_sharded else 0,
                    item_sharded,
                )
            # oaplint: disable=stream-host-sync -- end-of-fit barrier so
            jax.block_until_ready((x_blocks, y))  # phase_timer sees walls
        # X stays block-sharded on device; the model gathers on demand
        # (offset bookkeeping ~ ALSResult cUserOffset/cItemOffset,
        # ALSDALImpl.cpp:529-575).  Y mirrors that when sharded; a
        # replicated Y reads the local copy on every process.
        summary = {
            "timings": timings, "accelerated": True,
            "block_parallel": True, "sharded_factors": True,
            "als_kernel": "grouped" if grouped is not None else "coo",
            "item_layout": "sharded" if item_sharded else "replicated",
            **self._block_summary(world),
        }
        psn.record(summary, timings, pol)
        if ckpt is not None:
            ckpt.record(summary)
        if item_sharded:
            return ALSModel(
                None, None, summary,
                sharded_user=(x_blocks, np.asarray(offsets), upb),
                sharded_item=(y, np.asarray(ioffsets), ipb),
            )
        return ALSModel(
            None, np.asarray(y), summary,
            sharded_user=(x_blocks, np.asarray(offsets), upb),
        )
