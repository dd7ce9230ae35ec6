"""K-Means compute kernels: jitted Lloyd loop + initialization.

Replaces the reference's distributed Lloyd implementation
(native/KMeansDALImpl.cpp): there, each iteration broadcasts serialized
centroids (:49-59), runs oneDAL ``kmeans::Distributed<step1Local>`` per rank
(:70-77), allgathervs partials (:97-99), merges on the root (:101-131), and
the root does a manual per-center convergence test — squared-L2 move <= tol^2
(:135-168) — then broadcasts the converged flag (:213-214).

TPU-first redesign:
- Distances via the matmul identity ``|x|^2 + |c|^2 - 2 x @ c^T`` — the
  O(n*k*d) work lands on the MXU as one (n,d)x(d,k) matmul per iteration.
- Assignment one-hots are contracted back against X with a second matmul
  to get per-cluster sums — also MXU work, no scatters.
- The whole Lloyd loop is one ``lax.while_loop`` inside one jit: convergence
  is decided on device, no host round-trips per iteration (the reference
  pays a JNI + CCL round per phase).
- Cross-device reduction (per-cluster sums/counts/cost over the row-sharded
  table): the estimator's loop on a mesh is one ``shard_map`` in which
  every device accumulates its own row shard and ``psum`` sums the moments
  over the ``data`` axis (:func:`lloyd_run` with a ``mesh``); the k-means||
  rounds and a :func:`lloyd_run` without one on sharded arrays are global
  ``jnp.sum``/matmul/gather that GSPMD lowers to the same collectives.
  No root rank: results land replicated.
- Padded rows carry mask weight 0 so they never contribute (survey §2.6
  fixed-shape design note).

Weighted rows are supported natively (``mask`` doubles as a row-weight
vector), which the reference's DAL path cannot do (it falls back to vanilla
Spark when a weight column is set, spark-3.1.1/ml/clustering/KMeans.scala:349-351).
"""

from __future__ import annotations

import functools
from contextlib import nullcontext
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from oap_mllib_tpu.ops.pallas import _dbuf, autotune
from oap_mllib_tpu.ops.pallas import kmeans_kernel as kk
from oap_mllib_tpu.ops.pallas._tiers import check_mode, kernel_launch
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.parallel import collective
from oap_mllib_tpu.telemetry import spans
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.jax_compat import shard_map


def _prec(precision: str):
    """Map config's matmul_precision to a lax.Precision.

    "highest" (default) keeps full f32 on the MXU via multi-pass
    accumulation — required for the 1e-4 parity contract (survey §7.3
    determinism note).  "high" = bf16_3x sums + bf16 assignment (see
    _assign_prec) — measured within 1e-5 of highest on the parity suite;
    "default" (bf16 everywhere) measured ~1e-3 — outside the bar.
    Unknown values raise — a typo must not silently degrade to bf16."""
    try:
        return {
            "highest": lax.Precision.HIGHEST,
            "high": lax.Precision.HIGH,
            "default": lax.Precision.DEFAULT,
        }[precision]
    except KeyError:
        raise ValueError(
            "matmul_precision must be 'highest', 'high', or 'default', "
            f"got {precision!r}"
        ) from None


# Resident-block bounds of the fused kernel (padded elements): the full
# (k, d) centers AND sums blocks plus the (tile, k) distance/one-hot
# temporaries live in VMEM for the whole walk.  Fitted against the
# chip's compiler under the plane's scoped-VMEM ceiling
# (ops/pallas/_tiers.VMEM_LIMIT_BYTES): every (k_pad, d_pad) inside
# these bounds compiles at all three tiers (tests/test_tpu_compile.py
# holds the edge); just outside, k=16384 at d=256 needs 185 MB of the
# core's 128 and k=d=2048 at "high" 124.
PALLAS_MAX_KD = 1 << 21
PALLAS_MAX_K = 4096
PALLAS_MAX_D = 4096


def pallas_preferred(d: int, k: int, precision: str) -> bool:
    """Shape/tier rule for kmeans_kernel="auto": the fused Pallas kernel
    at every tier whose resident blocks fit VMEM.  Its loop-mode
    half-score assignment + exact-split sums pay 1+2 bf16 passes where
    XLA "high" pays 3+3 and "highest" 6+6; "default" (= the bf16 compute
    policy via precision.kernel_tier) prices ON Pallas too since the
    counts run as bf16 matmuls (kmeans_kernel._tile_update).

    Large k·d is excluded (bounds above): those fits stay on the chunked
    XLA path."""
    k_pad = -(-k // 128) * 128
    d_pad = -(-d // 128) * 128
    if (k_pad * d_pad > PALLAS_MAX_KD or k_pad > PALLAS_MAX_K
            or d_pad > PALLAS_MAX_D):
        return False
    return precision in ("highest", "high", "default")


def ring_mode_cfg(cfg=None) -> str:
    """Validated Config.ring_reduction.  Called on EVERY accelerated
    K-Means dispatch — single-device included, where the knob has no
    routing effect — so a typo raises everywhere (the als_item_layout
    contract: it must not surface only once deployed to a mesh)."""
    from oap_mllib_tpu.config import get_config

    cfg = cfg or get_config()
    mode = cfg.ring_reduction
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"ring_reduction must be auto|on|off, got {mode!r}"
        )
    return mode


def ring_enabled(mesh, data_axis: str, cfg=None) -> bool:
    """Resolve Config.ring_reduction for a mesh: the ring-fused moments
    reduction (ops/pallas/ring_reduce) runs by default ("auto"/"on")
    whenever the reduce axis actually has >= 2 devices, and falls back
    cleanly to the psum path below that — the acceptance contract."""
    return ring_mode_cfg(cfg) != "off" and mesh.shape[data_axis] >= 2


def _assign_prec(precision: str) -> str:
    """Precision for the ASSIGNMENT (distance) matmul inside the Lloyd
    loop.  The "high" tier runs it at bf16: argmin is a discrete decision
    — extra mantissa bits only matter at exact Voronoi ties, where either
    choice leaves the objective unchanged (cost is continuous across the
    boundary) — while centroid accuracy is governed by the SUMS matmul,
    which keeps bf16_3x.  Measured on TPU v5e (1M x 256, k=1000, blob
    data): bit-identical centers to dist-at-bf16_3x, 1.65x faster.
    "highest" stays full-f32 end-to-end (the strict parity tier)."""
    return "default" if precision == "high" else precision


def pairwise_sq_dists(
    x: jax.Array, centers: jax.Array, precision: str = "highest",
    policy: str = "f32",
) -> jax.Array:
    """(n, k) squared euclidean distances via the MXU-friendly identity.

    ``policy`` (utils/precision.py) governs the cross matmul: bf16 casts
    both operands (no-op when staging already delivered bf16 chunks) and
    accumulates f32; the squared norms ALWAYS reduce in f32 —
    ``psn.upcast`` is a no-op for f32/f64 inputs, so the default policy
    is bit-compatible with the pre-policy code."""
    xf = psn.upcast(x)
    cf = psn.upcast(centers)
    x_sq = jnp.sum(xf * xf, axis=1, keepdims=True)  # (n, 1)
    c_sq = jnp.sum(cf * cf, axis=1)  # (k,)
    cross = psn.pdot(x, centers.T, policy, precision)  # (n, k)  <- MXU
    d2 = x_sq + c_sq[None, :] - 2.0 * cross
    return jnp.maximum(d2, 0.0)


def argmin_rows(score: jax.Array, row_min=None, exact: bool = True):
    """``jnp.argmin(score, axis=1)`` — lowest index on ties, first NaN
    wins — as two plain reductions: the row minimum, then the first
    column that attains it.  ``row_min`` takes a ``jnp.min(score,
    axis=1)`` the caller already has.

    Why not ``jnp.argmin``: XLA:TPU types the value output of its
    (value, index) reduction ``bf16[rows]`` (the compiled text shows it,
    tests/test_tpu_compile.py::TestAssignment), and on a v5e candidates
    within bfloat16's step of each other then tie and the lower id wins
    whatever the f32 distances say.  Here the minimum is an f32
    reduction with an f32 consumer, and the index reduction is over
    integers.  ``exact=False`` is ``jnp.argmin`` itself, for a sheet
    that is not f32-exact to begin with (:func:`_exact_assign`)."""
    if not exact:
        return jnp.argmin(score, axis=1)
    m = jnp.min(score, axis=1) if row_min is None else row_min
    k = score.shape[1]
    ids = lax.broadcasted_iota(jnp.int32, score.shape, 1)
    hit = (score <= m[:, None]) | (score != score)
    first = jnp.min(jnp.where(hit, ids, k - 1), axis=1)
    return first.astype(jax.dtypes.canonicalize_dtype(np.int64))


def _exact_assign(assign_prec: str, policy: str) -> bool:
    """Whether a Lloyd assignment sheet is f32-exact and so gets the f32
    comparison of :func:`argmin_rows`: the strict-parity tier only.  The
    fast tiers' sheet comes from a bf16 (or bf16_3x) matmul and keeps
    ``jnp.argmin`` — the second pass over the sheet cost the XLA
    accumulate 7% at ``highest`` and 76% at ``high`` on a v5e (1M x 256,
    k=1000; PERF.md, PR 21), and those tiers exist for their speed."""
    return policy == "f32" and assign_prec == "highest"


def assign_clusters(x: jax.Array, centers: jax.Array) -> jax.Array:
    """(n,) argmin cluster ids."""
    return argmin_rows(pairwise_sq_dists(x, centers))


def _accumulate(x, weights, centers, precision: str = "highest",
                need_cost: bool = True, policy: str = "f32"):
    """One assignment pass: per-cluster weighted sums, counts, and cost.

    Returns (sums (k,d), counts (k,), cost scalar).  All reductions are
    global over the row-sharded inputs — GSPMD inserts the psum.

    ``need_cost=False`` is the Lloyd-loop-body mode: cost is dead inside
    the loop (the caller recomputes it at "highest" after convergence), so
    the assignment ranks on the half-score ``|c|^2/2 - x.c`` — argmin is
    invariant to the per-row |x|^2 term — skipping the d2 assembly and the
    min reduction entirely.

    ``policy`` (utils/precision.py): bf16 runs the assignment AND
    centroid-sum matmuls on bf16 operands with f32 accumulation — the
    one-hot/weights/counts/cost side stays f32 (``weights.dtype``), so
    the f32 accumulator contract holds whatever dtype the chunk arrived
    in (streamed bf16 staging included).  The default is bit-compatible
    with the pre-policy code.
    """
    k = centers.shape[0]
    aprec = _assign_prec(precision)
    exact = _exact_assign(aprec, policy)
    if need_cost:
        d2 = pairwise_sq_dists(x, centers, aprec, policy)  # (n, k)
        min_d2 = jnp.min(d2, axis=1)  # (n,)
        assign = argmin_rows(d2, min_d2, exact)  # (n,)
        cost = jnp.sum(min_d2 * weights)
    else:
        cf = psn.upcast(centers)
        c_sq = jnp.sum(cf * cf, axis=1)  # (k,)
        cross = psn.pdot(x, centers.T, policy, aprec)
        assign = argmin_rows(
            0.5 * c_sq[None, :] - cross, exact=exact
        )  # (n,)
        cost = jnp.asarray(0.0, weights.dtype)
    one_hot = (
        jax.nn.one_hot(assign, k, dtype=weights.dtype)
        * weights[:, None]
    )  # (n, k) — accum dtype: the bf16 policy must not round counts
    sums = psn.pdot(one_hot.T, x, policy, precision)  # (k, d)  <- MXU
    counts = jnp.sum(one_hot, axis=0)  # (k,)
    return sums, counts, cost


def _accumulate_chunked(x, weights, centers, row_chunks: int,
                        precision: str = "highest", need_cost: bool = True,
                        policy: str = "f32"):
    """Chunked assignment pass: bounds the live (chunk, k) distance/one-hot
    buffers so n*k never materializes in HBM (needed for bench-scale runs
    like 1M x 256 with k=1000, where (n, k) f32 alone is 4 GB).

    NOTE the rows must be local: the reshape assumes the leading dim can
    be freely split, which conflicts with GSPMD row-sharding.  On a mesh
    :func:`lloyd_run` calls this inside its ``shard_map``, where ``x`` is
    one device's shard.
    """
    n = x.shape[0]
    if n % row_chunks != 0:
        raise ValueError(f"rows {n} not divisible by row_chunks={row_chunks}")
    cs = n // row_chunks
    xc = x.reshape(row_chunks, cs, x.shape[1])
    wc = weights.reshape(row_chunks, cs)

    def step(carry, chunk):
        sums, counts, cost = carry
        xi, wi = chunk
        s, c, t = _accumulate(xi, wi, centers, precision, need_cost, policy)
        return (sums + s, counts + c, cost + t), None

    k, d = centers.shape[0], x.shape[1]
    # carries in the ACCUM dtype (weights), not x's: the bf16 policy's
    # per-chunk partials are f32 and must stay f32 across chunks (for
    # the f32/f64 paths weights.dtype == x.dtype — bit-compatible)
    zero = (
        jnp.zeros((k, d), weights.dtype),
        jnp.zeros((k,), weights.dtype),
        jnp.asarray(0.0, weights.dtype),
    )
    (sums, counts, cost), _ = lax.scan(step, zero, (xc, wc))
    return sums, counts, cost


# live-buffer element budget shared by every row-chunking site (training
# accumulate, predict/cost scoring, ALS recommend top-k): 32M f32 = 128 MB
# HBM.  One constant so a device-tier retune cannot leave the inference
# side inconsistent with training.
SCORE_BUDGET_ELEMS = 1 << 25


def rows_per_chunk(*widths: int, budget: int = SCORE_BUDGET_ELEMS) -> int:
    """Rows per scoring chunk such that the SUM of live (rows, width)
    buffers — input chunk + score/distance block — stays within budget.
    Bounding only the widest buffer would let the other grow unbounded
    (e.g. a (rows, d) input chunk at tiny k)."""
    return max(1, budget // max(1, sum(widths)))


def auto_row_chunks(n: int, k: int, budget_elems: int = SCORE_BUDGET_ELEMS) -> int:
    """Pick a chunk count so the live (chunk, k) distance buffer stays
    under ``budget_elems`` (default 32M f32 = 128 MB HBM).

    The budget is a HARD bound now: the count no longer needs to divide
    ``n`` — ``lloyd_run`` pads rows (weight 0) to the next chunk
    multiple.  (Previously an odd / non-power-of-two-divisible ``n``
    silently returned 1 chunk, letting the (n, k) buffer blow straight
    past the budget it exists to enforce.)  The bench shape (1M x 256,
    k=1000) gets 32 chunks, small fits 1 (no scan overhead).
    """
    chunks = 1
    while chunks < max(n, 1) and (-(-n // chunks)) * k > budget_elems:
        chunks *= 2
    return chunks


def _lloyd_loop(accum, moved_reduce, init_centers, max_iter, tol_sq):
    """The Lloyd loop skeleton of every in-memory route — XLA accumulate,
    Pallas walk, model-sharded — one definition so convergence and
    empty-cluster semantics cannot drift.

    Reference semantics (KMeansDALImpl.cpp:135-168): stop when every
    center's squared L2 move <= tol^2, or at max_iter.  Empty clusters
    keep their previous center (Spark MLlib behavior).  ``accum(centers,
    prec)`` returns (sums, counts, cost) for whichever layout the caller
    closed over; ``moved_reduce`` completes the per-center move norm
    (identity, or a psum over the model axis for feature-sharded centers).
    The final cost/counts are re-computed against the returned centers at
    full precision: the fast tiers' distance error is amplified by
    cancellation when clusters are tight, and the user-facing objective
    must not carry it (centers themselves stay ~1e-6 accurate).
    """

    def cond(state):
        _, it, converged = state
        return jnp.logical_and(it < max_iter, jnp.logical_not(converged))

    def body(state):
        centers, it, _ = state
        sums, counts, _ = accum(centers, None)
        safe = counts[:, None] > 0
        new_centers = jnp.where(
            safe, sums / jnp.maximum(counts[:, None], 1e-30), centers
        )
        moved_sq = moved_reduce(jnp.sum((new_centers - centers) ** 2, axis=1))
        converged = jnp.all(moved_sq <= tol_sq)
        return new_centers, it + 1, converged

    init_state = (init_centers, jnp.asarray(0, jnp.int32), jnp.asarray(False))
    centers, n_iter, _ = lax.while_loop(cond, body, init_state)
    _, counts, cost = accum(centers, "highest")
    return centers, n_iter, cost, counts


def _xla_accum(x, weights, row_chunks, precision, policy):
    """``accum(centers, prec)`` of :func:`_lloyd_loop` over the rows at
    hand with the chunked XLA accumulate.  The rows must be local (one
    device's table or, inside the ``shard_map``, one device's shard):
    the scan's reshape splits the leading dim."""
    # rows that don't divide the chunk count pad with weight-0 rows HERE
    # — once per compiled program, outside the while_loop, so the copy
    # cannot re-run per iteration — keeping auto_row_chunks' budget a
    # hard bound for any n (bucketed tables are already divisible and
    # skip this)
    pad = (-x.shape[0]) % row_chunks
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
        weights = jnp.concatenate(
            [weights, jnp.zeros((pad,), weights.dtype)]
        )

    def accum(centers, prec):
        # prec None = loop-body mode: no cost (recomputed at "highest" after
        # convergence), half-score assignment.  The final cost pass also
        # drops back to the f32 policy when the table itself is full
        # precision (in-memory fits): the user-facing objective should not
        # carry the fast policy's rounding when exact inputs are at hand —
        # streamed bf16-staged chunks keep the policy (x IS bf16 there).
        p = prec or precision
        need_cost = prec is not None
        pol = (
            "f32" if need_cost and x.dtype != jnp.bfloat16 else policy
        )
        if row_chunks > 1:
            return _accumulate_chunked(
                x, weights, centers, row_chunks, p, need_cost, pol
            )
        return _accumulate(x, weights, centers, p, need_cost, pol)

    return accum


def _walk_accum(x_p, w_p, live, mode, interpret, tile_rows, depth):
    """``accum(centers, prec)`` of :func:`_lloyd_loop` over the fused
    tile walk (ops/pallas/kmeans_kernel) on operands already in its
    padded layout, every pass ending at tile ``live``
    (``kmeans_kernel.live_tiles``); the kernel's ``(1, k_pad)`` counts
    and ``(1, 1)`` cost come back as the ``(k_pad,)`` and scalar the
    loop expects."""

    def accum(centers, prec):
        need_cost = prec is not None
        scope = "kmeans.lloyd_cost" if need_cost else "kmeans.lloyd_walk"
        with jax.named_scope(scope):
            sums, counts, cost = kk._accumulate_walk_any(
                x_p, w_p, centers, prec or mode, interpret, need_cost,
                tile_rows, depth, live,
            )
        return sums, counts[0], cost[0, 0]

    return accum


def _build_lloyd(mesh, dax, shards, max_iter, precision, policy, walk,
                 tile_rows, depth, interpret, row_chunks):
    """Build THE jitted Lloyd program of the in-memory routes (cached by
    :func:`lloyd_run`): :func:`_lloyd_loop` over one accumulate, on one
    device or on every shard of the data axis.

    ``walk``: the fused kernel of ops/pallas/kmeans_kernel (the DMA walk
    on the TPU or under ``interpret``, its schedule-identical XLA loop
    elsewhere) over operands padded to its layout once, before the loop,
    inside this program; else the chunked XLA accumulate.  The walk's
    bound — one past the last tile that holds a non-zero weight — is
    read once too, from the padded weights (each shard its own), and
    every iteration and the cost pass end there; the program returns it
    after ``(centers, n_iter, cost, counts)`` as ``int32[shards]`` (0 a
    shard on the XLA route, which walks no tile).

    ``shards == 1``: the program is jitted directly and emits no
    collective.  More: the whole loop runs inside ONE ``shard_map`` over
    ``dax`` — the reference's distributed step (local step on each rank,
    allgather of the partials, master step on the root;
    KMeansDALImpl.cpp:70-131) as one program: every device accumulates
    ITS row shard, ``collective.psum`` sums the moments (sums and counts
    an iteration, counts and cost after the final pass), and the centre
    update and the convergence test run replicated on the summed values.
    No root rank."""

    def reduced(accum):
        if shards == 1:
            return accum

        def over_shards(centers, prec):
            sums, counts, cost = accum(centers, prec)
            if prec is None:
                sums, counts = collective.psum((sums, counts), dax)
            else:
                counts, cost = collective.psum((counts, cost), dax)
            return sums, counts, cost

        return over_shards

    def program(x, weights, c0, tol):
        k, d = c0.shape
        if walk:
            x_p, w_p, c0 = kk._pad_operands_traced(
                x, weights, c0, block_rows=tile_rows
            )
            live = kk.live_tiles(w_p, tile_rows)
            accum = _walk_accum(
                x_p, w_p, live, precision, interpret, tile_rows, depth
            )
        else:
            live = jnp.int32(0)
            accum = _xla_accum(x, weights, row_chunks, precision, policy)
        centers, n_iter, cost, counts = _lloyd_loop(
            reduced(accum), lambda m: m, c0, max_iter, tol * tol
        )
        # the walk's lane padding comes off (nothing to cut on the XLA route)
        return centers[:k, :d], n_iter, cost, counts[:k], live.reshape(1)

    if shards == 1:
        return jax.jit(program)
    from jax.sharding import PartitionSpec as P

    return jax.jit(
        shard_map(
            program,
            mesh=mesh,
            in_specs=(P(dax, None), P(dax), P(), P()),
            out_specs=(P(), P(), P(), P(), P(dax)),
            check_vma=False,
        )
    )


def lloyd_shards(mesh, data_axis: str) -> int:
    """Row shards a Lloyd program reduces over: the data axis of a mesh
    of more than one device; 1 — one jitted program, no collective — on
    one device or without a mesh."""
    if mesh is None or mesh.devices.size == 1:
        return 1
    return mesh.shape[data_axis]


# what summary.kernel can say: the feature-sharded program, the fused
# Pallas walk, the chunked XLA accumulate
LLOYD_ROUTES = ("model_sharded", "pallas", "xla")


class LloydRoute(NamedTuple):
    """:func:`lloyd_route`'s answer; :func:`lloyd_run` takes it as
    ``row_chunks``, ``accumulate=kernel`` and ``**geometry``."""

    kernel: str  # one of LLOYD_ROUTES: what summary.kernel reports
    shards: int  # row shards the loop reduces over (1: no collective)
    row_chunks: int  # scan chunks of the XLA accumulate over a shard's rows
    geometry: Dict[str, int]  # tile_rows/depth (model_sharded: segments)


def _row_chunks(rows: int, k: int, geometry, degraded: int) -> int:
    """Chunk count of the XLA Lloyd's scan over ``rows`` resident rows
    (the table on one device, one shard on a mesh)."""
    if geometry != autotune.DEFAULTS["kmeans"]:
        # tuned bucket: chunk the scan at the tuned tile rows (the
        # default geometry keeps auto_row_chunks' occupancy rule
        # bit-for-bit, so untuned fits are unchanged)
        row_chunks = max(1, -(-rows // max(geometry["tile_rows"], 1)))
    else:
        row_chunks = auto_row_chunks(rows, k)
    if degraded:
        # auto_row_chunks returns a chunk COUNT — each geometric rung of
        # the ladder doubles it again, halving the rows (and the live
        # (chunk, k) buffer) per scan step
        row_chunks = min(row_chunks * (2 ** int(degraded)), max(rows, 1))
    return row_chunks


def lloyd_route(cfg, mesh, rows, d: int, k: int, dtype, precision: str,
                degraded: int = 0, checkpoint: bool = False,
                backend: str = None, processes: int = None) -> LloydRoute:
    """The one place that decides which Lloyd program a fit runs, from
    what can be observed, and the one validator of ``Config.kmeans_kernel``
    and ``Config.ring_reduction`` — called on EVERY accelerated fit, so a
    typo raises even where its answer is moot (a streamed fit, a single
    device).

    ``mesh`` None: the rows are streamed (ops/stream_ops runs its own
    chunked XLA passes): ``"xla"``.  A model axis > 1: the feature-sharded
    program — unless ``kmeans_kernel="xla"`` is forced, which runs the
    data-parallel program with the model axis holding replicas, so the
    two can be A/B'd on one mesh.  Else the fused walk when it is
    configured (``"pallas"``) or preferred (``"auto"`` and
    :func:`pallas_preferred`: the resident blocks fit VMEM) AND its
    preconditions hold: a TPU ``backend``, one process, f32, and neither
    of the estimator's two facts — ``degraded`` (the resilience ladder's
    level after a device OOM: whole-table residency is what OOMed, and
    each level doubles the XLA chunk count) and ``checkpoint`` (an armed
    checkpoint segments the loop between compiled calls).  The device
    count is no precondition: on a mesh every device walks its own shard.

    ``precision`` is the kernel tier the compute-precision policy maps
    to (utils/precision.kernel_tier).  ``rows`` are the table's padded
    rows; None (the table does not exist yet, or never will) names the
    route without resolving tile geometry or chunks.  ``backend`` and
    ``processes`` default to what jax reports."""
    kernel_cfg = cfg.kmeans_kernel
    if kernel_cfg not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"kmeans_kernel must be auto|xla|pallas, got {kernel_cfg!r}"
        )
    ring_mode_cfg(cfg)
    if mesh is None:
        return LloydRoute("xla", 1, 0, {})
    if mesh.shape[cfg.model_axis] > 1 and kernel_cfg != "xla":
        # segmented-start ring epilogue geometry: pure function of
        # (config, cache, bucket) so every rank resolves identically
        geometry = {} if rows is None else autotune.resolve(
            "ring", autotune.shape_bucket(mesh.shape[cfg.data_axis], d)
        )
        return LloydRoute(
            "model_sharded", mesh.shape[cfg.data_axis], 1, geometry
        )
    want = kernel_cfg == "pallas" or (
        kernel_cfg == "auto" and pallas_preferred(d, k, precision)
    )
    walk = (
        want
        and (backend or jax.default_backend()) == "tpu"
        and (processes or jax.process_count()) == 1
        and np.dtype(dtype) == np.float32
        and not degraded
        and not checkpoint
    )
    kernel = "pallas" if walk else "xla"
    shards = lloyd_shards(mesh, cfg.data_axis)
    if rows is None:
        return LloydRoute(kernel, shards, 0, {})
    # resolved for BOTH accumulates: the XLA Lloyd derives its chunking
    # from the same tile rows, so a tuned bucket steers either program
    geometry = autotune.resolve(
        "kmeans", autotune.shape_bucket(k, d), precision
    )
    row_chunks = (
        1 if walk else _row_chunks(rows // shards, k, geometry, degraded)
    )
    return LloydRoute(kernel, shards, row_chunks, geometry)


def lloyd_reduce_bytes(k: int, d: int, itemsize: int, n_iter: int,
                       walk: bool) -> int:
    """Bytes ONE device hands to the reductions of a data-parallel Lloyd
    run of ``n_iter`` iterations: ``(k, d)`` sums and ``(k,)`` counts an
    iteration, counts and the cost scalar after the final pass.  The
    walk reduces its lane-padded blocks (k and d up to multiples of
    128), the XLA accumulate the exact shapes."""
    if walk:
        k, d = -(-k // 128) * 128, -(-d // 128) * 128
    return (n_iter * (k * d + k) + k + 1) * itemsize


def _book_reductions(shards, rows_per_shard, k, d, itemsize, n_iter, walk):
    """What one launch of a data-parallel program reduced, booked once it
    has returned: the program runs its iterations on the device and only
    then says how many there were.  A checkpointed fit launches several
    segments; the ``lloyd_loop`` span sums their bytes."""
    n_iter = int(spans.fetch(np.asarray, n_iter))
    nbytes = lloyd_reduce_bytes(k, d, itemsize, n_iter, walk)
    collective.note_in_program(
        "psum",
        n_iter + 1,  # + the final cost pass
        nbytes * max(1, shards // jax.process_count()),
    )
    span = spans.current_span()
    if span is not None:
        span.attrs["shards"] = shards
        span.attrs["rows_per_shard"] = rows_per_shard
        span.attrs["reduce_bytes"] = (
            span.attrs.get("reduce_bytes", 0) + nbytes
        )


def lloyd_run(
    x: jax.Array,
    weights: jax.Array,
    init_centers: jax.Array,
    max_iter: int,
    tol: jax.Array,
    row_chunks: int = 1,
    precision: str = "highest",
    timings=None,
    phase: str = "lloyd_loop",
    policy: str = "f32",
    *,
    mesh=None,
    data_axis: str = "data",
    model_axis: str = "model",
    accumulate: str = "xla",
    tile_rows: int = autotune.DEFAULTS["kmeans"]["tile_rows"],
    depth: int = autotune.DEFAULTS["kmeans"]["depth"],
    segments: int = 1,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Full Lloyd optimization: returns (centers, n_iter, cost, counts).
    The one entry of every in-memory route; semantics in
    :func:`_lloyd_loop` (the reference's convergence contract,
    KMeansDALImpl.cpp:135-168).

    ``accumulate`` names the route, in :func:`lloyd_route`'s vocabulary
    (pass its answer: ``accumulate=route.kernel, **route.geometry``):
    ``"xla"`` the chunked XLA accumulate at ``row_chunks`` chunks of the
    resident rows, under ``policy`` (utils/precision.py); ``"pallas"``
    the fused tile walk at ``tile_rows``/``depth`` (f32 only;
    ``interpret`` runs the DMA kernel's program structure on the CPU);
    ``"model_sharded"`` the feature-sharded program
    (:func:`lloyd_run_model_sharded`, ``segments`` its ring epilogue's).

    ``mesh`` of more than one device: the table is row-sharded on
    ``data_axis``, every device accumulates its own shard and the
    moments are all-reduced (:func:`_build_lloyd`; centres, cost and
    counts come back replicated; a model axis holds replicas).  One
    device, or no mesh: one jitted program over the arrays as they lie —
    on sharded arrays GSPMD places it.

    The program is built once per (world, statics) in the program-cache
    registry and each launch is registered there, so fits report how
    many programs they compiled vs reused; ``timings`` (when given)
    receives the ``<phase>/compile`` / ``<phase>/execute`` wall split.
    A launch of the walk notes the kernel's bf16 MXU passes a tile on the
    active span (``mxu_passes``: ``{"cross": 6, "sums": 3}`` at
    ``highest``; kmeans_kernel.MXU_PASSES) and the tiles it walked:
    ``walk_tiles`` a shard's padded rows hold, ``walk_tiles_live`` where
    the device's bound ended every pass (the fullest shard's; reading it
    waits for the program, inside the span's ``fetch`` leaf, as every
    blocking read of the phase is).  A launch on more than one
    shard books what it reduced (``oap_collective_ops_total{op="psum"}``,
    the active span's ``shards`` / ``rows_per_shard`` / ``reduce_bytes``)
    once it has returned, which waits for its iteration count.
    """
    if accumulate not in LLOYD_ROUTES:
        raise ValueError(
            f"accumulate must be one of {LLOYD_ROUTES}, got {accumulate!r}"
        )
    if accumulate == "model_sharded":
        return lloyd_run_model_sharded(
            x, weights, init_centers, max_iter, tol, mesh, data_axis,
            model_axis, precision, timings, phase, policy,
            ring_segments=segments,
        )
    walk = accumulate == "pallas"
    shards = lloyd_shards(mesh, data_axis)
    # only what the chosen accumulate reads keys its program
    if walk:
        tier = check_mode(precision)
        statics = (policy, True, int(tile_rows), _dbuf.check_depth(depth),
                   bool(interpret), 1)
    else:
        statics = (policy, False, 0, 0, False, int(row_chunks))
    world = (
        (progcache.mesh_fingerprint(mesh), data_axis) if shards > 1
        else progcache.backend_fingerprint()
    )
    fn = progcache.get_or_build(
        "kmeans.lloyd", (world, shards, max_iter, precision) + statics,
        lambda: _build_lloyd(
            mesh, data_axis, shards, max_iter, precision, *statics
        ),
    )
    key = (
        world, progcache.array_key(x, weights),
        np.shape(init_centers), max_iter, precision,
    ) + statics
    # the walk books a Pallas wrapper dispatch like every kernel entry
    booked = kernel_launch("kmeans.lloyd_loop") if walk else nullcontext()
    with progcache.launch("kmeans.lloyd_run", key, timings, phase), booked:
        *out, live = fn(x, weights, jnp.asarray(init_centers), tol)
    span = spans.current_span()
    if walk and span is not None:
        # what the kernel issues a tile, for the reader of lloyd_roofline
        span.attrs["mxu_passes"] = dict(kk.MXU_PASSES[tier])
        # a shard's tiles, and where the device's own bound ended its
        # walk (the fullest shard's): passes x live tiles is its work
        span.attrs["walk_tiles"] = kk.walk_tiles(
            x.shape[0] // shards, tile_rows
        )
        span.attrs["walk_tiles_live"] = int(
            spans.fetch(np.asarray, live).max()
        )
    if shards > 1:
        k, d = np.shape(init_centers)
        _book_reductions(
            shards, x.shape[0] // shards, k, d, np.dtype(x.dtype).itemsize,
            out[1], walk,
        )
    return tuple(out)


def _lloyd_model_sharded_fn(mesh, dax: str, max_: str, max_iter: int,
                            precision: str, policy: str = "f32",
                            ring: bool = False, ring_segments: int = 1):
    """Compiled model-sharded Lloyd program, cached in the process-wide
    program registry (utils/progcache — this function's old private
    functools.lru_cache is the pattern the registry generalizes) per
    (mesh fingerprint, shape-free statics): a fresh jit(shard_map)
    closure per fit would recompile."""
    key = (
        progcache.mesh_fingerprint(mesh), dax, max_, max_iter, precision,
        policy, ring, ring_segments,
    )
    return progcache.get_or_build(
        "kmeans.lloyd_model_sharded", key,
        lambda: _build_lloyd_model_sharded(mesh, dax, max_, max_iter,
                                           precision, policy, ring,
                                           ring_segments),
    )


def _build_lloyd_model_sharded(mesh, dax: str, max_: str, max_iter: int,
                               precision: str, policy: str = "f32",
                               ring: bool = False, ring_segments: int = 1):
    """Build the jitted model-sharded Lloyd program (cached above).

    Mesh-sharded linalg (survey §5): on a (data, model) mesh each device
    holds a (rows/data, d/model) tile of X and a (k, d/model) tile of the
    centroids — the feature axis is split exactly like the model-sharded
    PCA Gram (pca_ops.covariance_model_sharded), so centroid blocks whose
    (k, d) outgrows one chip's HBM spread over the model axis.  Squared
    distances decompose additively over feature blocks, so the assignment
    needs ONE psum of the (n_loc, k) partial distances over the model axis;
    the centroid-sum matmul then stays entirely feature-local (each model
    shard updates its own slice) with a psum over data only.  The reference
    cannot shard this dimension at all (oneDAL centroids are single-node,
    KMeansDALImpl.cpp:101-131).

    ``ring=True`` replaces the three standalone data-axis psums of the
    accumulate (centroid sums, counts, cost) with ONE ring reduction of
    the packed (k, d_loc + 2) moments buffer
    (ops/pallas/ring_reduce.ring_allreduce — remote-DMA kernel on TPU,
    the identical-schedule ppermute program elsewhere); the model-axis
    assignment psum and the convergence-move psum are untouched.
    ``ring_segments`` > 1 splits the packed buffer into that many
    independently-fenced ring reductions (segmented-start epilogue, a
    tuned knob — see ring_allreduce's docstring).
    """
    world = mesh.shape[dax]

    def accum(x_blk, w_blk, c_blk, aprec, sprec, pol, need_cost):
        k = c_blk.shape[0]
        exact = _exact_assign(aprec, pol)
        cf = psn.upcast(c_blk)
        c_sq = jnp.sum(cf * cf, axis=1)  # (k,)
        cross = psn.pdot(x_blk, c_blk.T, pol, aprec)  # <- MXU
        if need_cost:
            xf = psn.upcast(x_blk)
            x_sq = jnp.sum(xf * xf, axis=1, keepdims=True)  # (n_loc, 1)
            # one psum carries all three feature-block partials at once
            d2 = collective.psum(x_sq + c_sq[None, :] - 2.0 * cross, max_)
            d2 = jnp.maximum(d2, 0.0)
            min_d2 = jnp.min(d2, axis=1)
            assign = argmin_rows(d2, min_d2, exact)
        else:
            # loop-body mode: rank on the half-score (argmin-invariant to
            # |x|^2); still ONE psum over the model axis, no d2/min passes
            score = collective.psum(0.5 * c_sq[None, :] - cross, max_)
            assign = argmin_rows(score, exact=exact)
        one_hot = (
            jax.nn.one_hot(assign, k, dtype=w_blk.dtype) * w_blk[:, None]
        )
        sums_part = psn.pdot(one_hot.T, x_blk, pol, sprec)  # (k, d_loc)
        counts_part = jnp.sum(one_hot, axis=0)  # (k,)
        cost_part = (
            jnp.sum(min_d2 * w_blk)
            if need_cost else jnp.asarray(0.0, w_blk.dtype)
        )
        if ring:
            # ONE packed ring reduction instead of three psums: columns
            # [0:d_loc] sums, d_loc counts, d_loc+1 the cost scalar (row
            # 0; zero elsewhere so the sum is exact)
            extra = jnp.zeros((k, 2), sums_part.dtype)
            extra = extra.at[:, 0].set(counts_part)
            if need_cost:
                extra = extra.at[0, 1].set(cost_part)
            from oap_mllib_tpu.ops.pallas.ring_reduce import ring_allreduce

            d_loc = sums_part.shape[1]
            red = ring_allreduce(
                jnp.concatenate([sums_part, extra], axis=1), dax, world,
                segments=ring_segments,
            )
            sums_blk = red[:, :d_loc]
            counts = red[:, d_loc]
            cost = (
                red[0, d_loc + 1]
                if need_cost else jnp.asarray(0.0, w_blk.dtype)
            )
        else:
            sums_blk = collective.psum(sums_part, dax)  # feature-local
            counts = collective.psum(counts_part, dax)
            cost = (
                collective.psum(cost_part, dax)
                if need_cost else jnp.asarray(0.0, w_blk.dtype)
            )
        return sums_blk, counts, cost

    def rank_program(x_blk, w_blk, c0_blk, tol_sq):
        def tile_accum(c_blk, prec):
            if prec == "highest":
                # final cost/counts pass: full precision against the f32
                # table (the in-memory contract — see _lloyd_run_jit)
                return accum(
                    x_blk, w_blk, c_blk, "highest", "highest", "f32", True
                )
            return accum(
                x_blk, w_blk, c_blk, _assign_prec(precision), precision,
                policy, False,
            )

        # per-center move norms are partial over the local feature block —
        # complete them over the model axis before the convergence test
        return _lloyd_loop(
            tile_accum, lambda m: collective.psum(m, max_), c0_blk, max_iter,
            tol_sq,
        )

    from jax.sharding import PartitionSpec as P

    return jax.jit(
        shard_map(
            rank_program,
            mesh=mesh,
            in_specs=(P(dax, max_), P(dax), P(None, max_), P()),
            out_specs=(P(None, max_), P(), P(), P()),
            check_vma=False,
        )
    )


def lloyd_run_model_sharded(
    x: jax.Array,
    weights: jax.Array,
    init_centers: jax.Array,
    max_iter: int,
    tol: jax.Array,
    mesh,
    data_axis: str,
    model_axis: str,
    precision: str = "highest",
    timings=None,
    phase: str = "lloyd_loop",
    policy: str = "f32",
    ring_segments: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Lloyd loop with centroids feature-sharded over the MODEL axis.

    Same semantics and return contract as :func:`lloyd_run`.  ``d`` must be
    a multiple of the model-axis size (the estimator zero-pads feature
    columns; zero columns contribute nothing to distances or moves, and
    their centroid entries stay exactly zero).

    The per-pass centroid moments reduce with the ring-fused path by
    default (:func:`ring_enabled`: Config.ring_reduction, >= 2 devices
    on the data axis, f32 — the ring packs/reduces in f32, so the x64
    parity lane keeps the psum path).
    """
    ring = ring_enabled(mesh, data_axis) and np.dtype(x.dtype) == np.float32
    ring_segments = max(1, int(ring_segments)) if ring else 1
    fn = _lloyd_model_sharded_fn(mesh, data_axis, model_axis, max_iter,
                                 precision, policy, ring, ring_segments)
    key = (
        progcache.mesh_fingerprint(mesh),
        progcache.array_key(x, weights),
        np.asarray(init_centers).shape, max_iter, precision, policy, ring,
        ring_segments,
    )
    with progcache.launch("kmeans.lloyd_model_sharded.run", key, timings,
                          phase):
        return fn(x, weights, jnp.asarray(init_centers), tol * tol)


@jax.jit
def total_cost(x: jax.Array, weights: jax.Array, centers: jax.Array) -> jax.Array:
    _, _, cost = _accumulate(x, weights, centers)
    return cost


@jax.jit
def min_sq_dists(x: jax.Array, centers: jax.Array) -> jax.Array:
    return jnp.min(pairwise_sq_dists(x, centers), axis=1)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------
# The reference deliberately reuses Spark's JVM-side init (random or
# k-means||) to produce initial centers before handing off to the native
# loop (spark-3.1.1/ml/clustering/KMeans.scala:388-410).  We implement both
# natively.  Parity is RNG-sensitive, so tests compare converged cost, not
# centers (survey §7.3).


def _to_host(a) -> np.ndarray:
    """Fetch a (possibly multi-host sharded) array to host.

    An unconstrained jit output on a multi-process mesh may come back
    sharded (not fully addressable), in which case np.asarray would raise —
    re-run it through an identity jit with an explicitly replicated output
    first (every process executes the same fetch collectively).
    """
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = a.sharding.mesh
        a = progcache.get_or_build(
            "kmeans.fetch_replicated",
            (progcache.mesh_fingerprint(mesh),),
            lambda: jax.jit(
                lambda v: v,
                out_shardings=NamedSharding(mesh, PartitionSpec()),
            ),
        )(a)
    return np.asarray(a)


def _row_shards(x) -> int:
    """How many row shards ``x`` lies in (1 for a host array)."""
    if not isinstance(x, jax.Array):
        return 1
    return x.shape[0] // x.sharding.shard_shape(x.shape)[0]


def _gather_rows(x, idx: np.ndarray) -> np.ndarray:
    """Fetch x[idx] to host; collective for multi-host global arrays.

    A multi-host sharded jax.Array is not fully addressable, so plain
    indexing cannot run on one host — every process executes the same
    jitted gather with a replicated output instead (all processes call
    init with the same seed, so the gathers agree).
    """
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = x.sharding.mesh
        gathered = progcache.get_or_build(
            "kmeans.gather_rows",
            (progcache.mesh_fingerprint(mesh),),
            lambda: jax.jit(
                lambda a, i: a[i],
                out_shardings=NamedSharding(mesh, PartitionSpec()),
            ),
        )(x, jnp.asarray(idx))
        return np.asarray(gathered)
    return np.asarray(x[idx])


def init_random(
    x, n_valid: int, k: int, seed: int, index_map=None
) -> np.ndarray:
    """Sample k distinct valid rows uniformly (Spark's initRandom analog).

    ``x`` may be a (sharded) jax.Array or ndarray; only the k selected rows
    are gathered/transferred, never the full table.  ``index_map`` converts
    valid-row indices to padded-layout indices (DenseTable.valid_to_padded)
    — without it, multi-host tables would sample mid-array padding rows.
    """
    rng = np.random.default_rng(seed)
    idx = rng.choice(n_valid, size=min(k, n_valid), replace=False)
    if len(idx) < k:  # fewer points than clusters: duplicate (degenerate case)
        idx = np.resize(idx, k)
    if index_map is not None:
        idx = index_map(idx)
    return _gather_rows(x, idx)


def _slot_chunk_size(cap: int, target: int = 512) -> int:
    """The slots a k-means|| round folds at a time: the largest divisor
    of ``cap`` in [target // 4, target], and for a ``cap`` that has none
    (4k for a prime k above the target) the largest divisor <= 2 *
    target, so that no k falls to a chunk of a few slots while one of
    about a thousand would do.

    A chunk bounds the live (n, chunk) distance sheet, and it is the
    step by which the fold follows the slots a round filled
    (:func:`_live_chunks`): about half of ``cap``, so a finer chunk ends
    the fold closer behind the last pick.  The target is a few MXU
    column tiles — cap = 4000 folds in chunks of 500, which fill 512
    columns as well as 1000 fill 1024 — and a chunk is not finer than
    one tile where ``cap`` allows, or a step's read of the table shows
    from under its product.

    Direct paired-divisor enumeration up to sqrt(cap): every divisor d
    <= sqrt(cap) pairs with cap // d, so scanning the square root covers
    them all."""
    fine, coarse = 0, 1
    d = 1
    while d * d <= cap:
        if cap % d == 0:
            for c in (d, cap // d):
                if target // 4 <= c <= target:
                    fine = max(fine, c)
                if c <= 2 * target:
                    coarse = max(coarse, c)
        d += 1
    return fine or coarse


def _live_chunks(filled, chunk: int):
    """Chunks of ``chunk`` slots that hold a filled one when the first
    ``filled`` slots are: what a round folds.  One formula for the
    device's loop bound and the host's count of it."""
    return (filled + chunk - 1) // chunk


@functools.partial(jax.jit, static_argnames=("cap", "chunk"))
@jax.named_scope("kmeans.pll_round")
def _pll_round(x, w, dmin, amin, base_id, key, l, cap, chunk):
    """One k-means|| sampling round, entirely on device.

    Samples each row with probability min(l * cost / phi, 1) (Bahmani
    oversampling; padded rows have w=0 so cost=0 and are never picked),
    gathers the picked rows into a fixed ``cap``-slot buffer, then folds
    the new slots into the running (min-distance, nearest-candidate)
    state ``chunk`` slots at a time, so no (n, cap) buffer ever
    materializes, and only as far as the slots are filled: the loop's
    trip count is :func:`_live_chunks` of the round's own pick count, not
    ``cap // chunk``, because a chunk with no valid slot is a full-price
    distance sheet that changes no row.  About half the capacity is never
    multiplied; a round whose picks reach ``cap`` folds every chunk.

    Slot ``j`` holds the row whose inclusive picked-prefix first reaches
    ``j + 1``: a binary search of the prefix for the ordinals ``1 ..
    cap`` (log2(n) steps of ``cap`` reads) and one gather of at most
    ``cap`` rows, where a scatter-add would walk every row of the table
    to place them.  Picks fill slots ``0 .. picks - 1`` in row order;
    picks past ``cap`` are dropped (cap is 2x the expected pick count);
    an ordinal past the last pick finds no row, and its slot is zeros
    with validity 0.  A picked value is copied, not added to zero, so an
    exact ``-0.0`` stays ``-0.0``: no distance changes by it.  Every
    reduction, the prefix and the gathers are global: under a row-sharded
    mesh GSPMD gathers on each shard's own rows and sums the slots with
    one all-reduce, so the round is multi-host-safe with zero O(n) host
    transfers.

    Returns (slots, slot_valid, new_dmin, new_amin, phi, picks); ``picks``
    counts the rows sampled, those past ``cap`` included.
    """
    cost = dmin * w
    phi = jnp.sum(cost)
    prob = jnp.minimum(l * cost / jnp.maximum(phi, 1e-30), 1.0)
    draws = jax.random.uniform(key, dmin.shape, dtype=dmin.dtype)
    picked = draws < prob
    prefix = jnp.cumsum(picked.astype(jnp.int32))  # inclusive, global
    picks = prefix[-1]
    ordinals = jnp.arange(1, cap + 1, dtype=jnp.int32)
    # an ordinal past the last pick finds index n: out of range -> zeros
    rows = jnp.searchsorted(prefix, ordinals, side="left")
    slots = x.at[rows].get(mode="fill", fill_value=0)
    filled = jnp.minimum(picks, cap)
    slot_valid = (ordinals <= filled).astype(x.dtype)

    # picks fill slots 0 .. picks-1: a chunk past the last filled slot is
    # a sheet of inf (cm < dm false on every row), so the fold ends there
    q = cap // chunk
    slots_c = slots.reshape(q, chunk, x.shape[1])
    valid_c = slot_valid.reshape(q, chunk)

    def fold(i, carry):
        dm, am = carry
        s = lax.dynamic_index_in_dim(slots_c, i, 0, keepdims=False)
        v = lax.dynamic_index_in_dim(valid_c, i, 0, keepdims=False)
        d2 = pairwise_sq_dists(x, s)
        d2 = jnp.where(v[None, :] > 0, d2, jnp.inf)
        cm = jnp.min(d2, axis=1)
        ca = argmin_rows(d2, cm).astype(jnp.int32) + base_id + chunk * i
        better = cm < dm
        return jnp.where(better, cm, dm), jnp.where(better, ca, am)

    dmin, amin = lax.fori_loop(
        0, _live_chunks(filled, chunk), fold, (dmin, amin)
    )
    return slots, slot_valid, dmin, amin, phi, picks


@functools.partial(jax.jit, static_argnames=("n_cand",))
@jax.named_scope("kmeans.candidate_weights")
def _candidate_weights(w, amin, n_cand: int):
    """Total row weight owned by each candidate (global segment-sum)."""
    return jnp.zeros((n_cand,), w.dtype).at[amin].add(w)


def _reduce_candidates(slots, weights, valid, key, k: int):
    """Weighted k-means++ over a fixed-shape candidate buffer, on the
    device: :func:`_weighted_kmeans_pp` step for step, one program.

    ``slots`` (m, d) hold the candidates, ``valid`` (m,) is positive where
    a slot holds one, ``weights`` (m,) the row weight each owns.  An empty
    slot weighs 0 and is never drawn.  Each of the k draws is an inverse
    CDF over ``p = d2 * w`` from one of k uniforms made before the loop;
    ``d2`` is the direct ``sum((slots - c) ** 2)``, as on the host, so no
    cancellation of the matmul identity enters.  The host's two
    degenerate cases keep their meaning: no weight at all -> every valid
    slot weighs 1; no mass left at a step (every candidate already a
    centre) -> a uniform draw among the valid slots.  Elementwise work
    and reductions in the slots' dtype (the table's: float32, float64
    under x64), no product."""
    dtype = slots.dtype
    live = (valid > 0).astype(dtype)
    w = weights.astype(dtype) * live
    w = jnp.where(jnp.sum(w) > 0, w, live)
    ids = jnp.arange(slots.shape[0], dtype=jnp.int32)
    u = jax.random.uniform(key, (k,), dtype)

    def draw(p, ui):
        p = jnp.where(jnp.sum(p) > 0, p, live)
        cdf = jnp.cumsum(p)
        idx = jnp.sum(cdf <= ui * cdf[-1]).astype(jnp.int32)
        # a prefix sum in parallel is not monotone to the last bit, and
        # ui * total can round up to the total: either can leave idx on a
        # slot of no mass or past the end, so settle on the nearest slot
        # WITH mass at or before it, else on the first one
        mass = p > 0
        before = jnp.max(jnp.where(mass & (ids <= idx), ids, -1))
        return jnp.where(before >= 0, before, jnp.argmax(mass))

    def pick(idx, d2):
        c = lax.dynamic_index_in_dim(slots, idx, 0, keepdims=False)
        return c, jnp.minimum(d2, jnp.sum((slots - c) ** 2, axis=1))

    def step(i, carry):
        centers, d2 = carry
        c, d2 = pick(draw(d2 * w, u[i]), d2)
        return lax.dynamic_update_index_in_dim(centers, c, i, 0), d2

    c, d2 = pick(draw(w, u[0]), jnp.full((slots.shape[0],), jnp.inf, dtype))
    centers = jnp.zeros((k, slots.shape[1]), dtype).at[0].set(c)
    centers, _ = lax.fori_loop(1, k, step, (centers, d2))
    return centers


def _reduce_candidates_fn(k: int, mesh=None):
    """The jitted :func:`_reduce_candidates`, one per (k, mesh) in the
    program registry.  On a ``mesh`` it runs replicated — inputs and
    output under ``PartitionSpec()``, what fetching the candidates to the
    host forced before — so every process runs the same program on the
    same key and holds the same centres."""
    shardings = {}
    world = progcache.backend_fingerprint()
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        shardings = {"in_shardings": rep, "out_shardings": rep}
        world = progcache.mesh_fingerprint(mesh)
    return progcache.get_or_build(
        "kmeans.reduce_candidates", (world, k),
        lambda: jax.jit(
            functools.partial(_reduce_candidates, k=k), **shardings
        ),
    )


def reduce_candidates(slots, weights, valid, key, k: int,
                      mesh=None) -> np.ndarray:
    """Reduce the k-means|| candidates to k centres on the device and
    fetch the (k, d) result: the one call both accelerated routes make."""
    # from the launch until the centres are on the host: the host has
    # nothing else to do meanwhile
    return spans.fetch(
        lambda: np.asarray(
            _reduce_candidates_fn(k, mesh)(slots, weights, valid, key)
        )
    )


@jax.jit
def _candidate_buffer(c0, slots, valids):
    """The rounds' slot buffers behind candidate 0, as one (m, d) buffer
    with its validity: the layout ``amin`` numbers the candidates in."""
    return (
        jnp.concatenate([c0.astype(slots[0].dtype), *slots], axis=0),
        jnp.concatenate([jnp.ones((1,), valids[0].dtype), *valids]),
    )


def init_kmeans_parallel(
    x_dev: jax.Array,
    weights_dev: jax.Array,
    n_valid: int,
    k: int,
    seed: int,
    init_steps: int = 2,
    index_map=None,
) -> np.ndarray:
    """k-means|| (Bahmani et al.) with oversampling l = 2k, Spark defaults.

    The candidate set lives in a static-shape device buffer (1 +
    4k*steps slots — 2x the expected 2k picks per round, so
    overflow-dropping is vanishingly rare; a round folds only the slot
    chunks its picks reached, and the ``rounds`` span says how many:
    ``slot_chunks`` of ``slot_chunks_cap``, over ``slots_filled`` slots,
    with ``picks_dropped`` picks past a round's capacity)
    and never goes to the host:
    per-round sampling/prefix-search/gather/min-fold run in one jitted
    program, the ownership weights in another, and the weighted
    k-means++ reduction of the candidates to k centres (Spark runs it on
    the driver, mllib/clustering/KMeans.scala initKMeansParallel) in a
    third, :func:`_reduce_candidates`.  The host fetches what it decides
    on — each round's ``phi`` and pick count, in one read — and the
    (k, d) centres.  Every device op is GSPMD-global, so the same code
    serves multi-host meshes.  Only a table that yields no more than k
    candidates is topped up with random rows, on the host.
    """
    rng = np.random.default_rng(seed)
    n, d = x_dev.shape

    # everything that waits on the device: the seed-row gather, the
    # rounds with the fetch of their phi and pick count, the candidate
    # weights (each blocking read an entry of the span's ``fetch`` leaf, each
    # call that hands the device a program one of its ``launch`` leaf:
    # what is left of the span is the host's Python between them)
    with spans.child("rounds") as span:
        # first center: uniform valid row (index_map: valid -> padded layout)
        first = np.asarray([rng.integers(n_valid)])
        if index_map is not None:
            first = np.asarray(index_map(first))
        c0 = spans.fetch(_gather_rows, x_dev, first)  # (1, d)

        l = jnp.asarray(2.0 * k, jnp.float32)  # Spark's oversampling factor
        cap = 4 * k  # per-round slot buffer
        chunk = _slot_chunk_size(cap)
        key = jax.random.PRNGKey(seed)

        # running state: distances/assignments vs candidate 0 — as one
        # program (the minimum over the one candidate is its distance):
        # op by op, ``x * x`` would stand whole beside the table
        dmin = spans.launch(min_sq_dists, x_dev, jnp.asarray(c0))
        amin = jnp.zeros((n,), jnp.int32)

        all_slots, all_valid, picks = [], [], []
        for step in range(init_steps):
            # the runtime can hold this call until the device has finished
            # what was queued before it (at 2^22 rows on a v5e 24 of its
            # 29 ms while the distances above ran op by op; 2 ms since)
            slots, slot_valid, dmin, amin, phi, picked = spans.launch(
                _pll_round, x_dev, weights_dev, dmin, amin,
                jnp.asarray(1 + cap * step, jnp.int32),
                jax.random.fold_in(key, step), l, cap, chunk,
            )
            phi, picked = spans.fetch(jax.device_get, (phi, picked))
            if float(phi) <= 0.0:
                break
            all_slots.append(slots)
            all_valid.append(slot_valid)
            picks.append(int(picked))
        # picks fill a round's slots from 0; those past cap were dropped
        filled = [min(p, cap) for p in picks]
        n_cand = 1 + sum(filled)
        rounds = len(all_slots)
        span.attrs["rounds"] = rounds
        span.attrs["shards"] = _row_shards(x_dev)
        # what the rounds' folds multiplied, against what the capacity
        # would have asked for
        span.attrs["slots_filled"] = sum(filled)
        span.attrs["picks_dropped"] = sum(picks) - sum(filled)
        span.attrs["slot_chunks"] = sum(_live_chunks(f, chunk) for f in filled)
        span.attrs["slot_chunks_cap"] = (cap // chunk) * rounds
        if n_cand > k:
            # rounds that did not run leave their slots empty, so the
            # buffer, and with it the programs below, keep one shape
            for _ in range(rounds, init_steps):
                all_slots.append(jnp.zeros_like(all_slots[0]))
                all_valid.append(jnp.zeros_like(all_valid[0]))
            cand, valid = spans.launch(
                _candidate_buffer, c0, tuple(all_slots), tuple(all_valid)
            )
            # waited for here: this span ends when its device work has,
            # and the reduction's span is not billed for the weights
            cand_w = spans.fetch(
                jax.block_until_ready,
                spans.launch(
                    _candidate_weights, weights_dev, amin, cand.shape[0]
                ),
            )

    # the reduction of the candidates to k centers (the span's name dates
    # from the host's loop; it ends when the centers are on the host)
    with spans.child("kmeanspp_host") as span:
        span.attrs["candidates"] = n_cand
        if n_cand > k:
            span.attrs["reduced_on"] = "device"
            # weight candidates by how much row weight they own
            return reduce_candidates(
                cand, cand_w, valid, jax.random.fold_in(key, init_steps), k,
                getattr(x_dev.sharding, "mesh", None),
            )
        # not enough candidates: top up with random rows, on the host
        span.attrs["reduced_on"] = "host"
        cand = np.concatenate(
            [np.asarray(c0)]
            + [_to_host(s)[:f] for s, f in zip(all_slots, filled)],
            axis=0,
        )
        extra = init_random(
            x_dev, n_valid, k - cand.shape[0] + 1, seed + 1, index_map
        )
        cand = np.concatenate([cand, extra], axis=0)[: max(k, 1)]
        return (
            cand[:k]
            if cand.shape[0] >= k
            else np.resize(cand, (k, cand.shape[1]))
        )


def _weighted_kmeans_pp(points: np.ndarray, weights: np.ndarray, k: int, rng) -> np.ndarray:
    """Host-side weighted k-means++ over the small candidate set."""
    n = points.shape[0]
    total = weights.sum()
    if total <= 0:
        weights = np.ones(n)
        total = float(n)
    centers = [points[rng.choice(n, p=weights / total)]]
    d2 = np.sum((points - centers[0]) ** 2, axis=1)
    for _ in range(1, k):
        p = d2 * weights
        s = p.sum()
        if s <= 0:
            idx = int(rng.integers(n))
        else:
            idx = int(rng.choice(n, p=p / s))
        centers.append(points[idx])
        d2 = np.minimum(d2, np.sum((points - points[idx]) ** 2, axis=1))
    return np.stack(centers)
