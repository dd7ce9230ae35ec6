"""Streamed (out-of-core) kernels: full-pass K-Means / PCA over a ChunkSource.

Device memory is bounded by O(chunk_rows x d) while the algorithms make
whole-table passes: each pass walks the source once, pushing fixed-shape
chunks through ONE compiled per-chunk program whose accumulators live on
device (donated, so XLA updates them in place).  This is the capability the
reference does not have — its executors must hold their whole partition as
a native table in RAM (OneDAL.scala:92-166) — and it is what lets a single
chip with 16 GB HBM fit the 100M x 256 north-star table (100 GB) streamed
from host RAM / disk.

Pass structure:
- K-Means: one pass per Lloyd iteration (loop-body mode: half-score
  assignment, no cost), one final pass at "highest" for cost/counts.
- k-means|| init: 1 reservoir pass + 1 distance pass + init_steps sampling
  passes + 1 ownership pass (the in-memory version's device state becomes a
  host-resident per-chunk dmin, updated lazily one round behind — Bahmani's
  oversampling is robust to the one-round-stale phi used for sampling).
- PCA: one pass for the column sums (mean), one for the centered Gram —
  the same two-pass mean-centered form as ops.pca_ops.covariance (the
  one-pass raw-moment form cancels catastrophically; see that docstring).
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from oap_mllib_tpu.data.prefetch import Prefetcher, PrefetchStats
from oap_mllib_tpu.data.stream import ChunkSource
from oap_mllib_tpu.ops import kmeans_ops
from oap_mllib_tpu.telemetry import fleet, flightrec
from oap_mllib_tpu.utils import faults
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils import recovery
from oap_mllib_tpu.utils import sanitizers
from oap_mllib_tpu.utils.timing import tick


def _chunk_weights(n_valid: int, chunk_rows: int, dtype) -> np.ndarray:
    w = np.zeros((chunk_rows,), dtype)
    w[:n_valid] = 1.0
    return w


def _iter_weighted(source: ChunkSource, weights, dtype):
    """Yield (chunk, n_valid, w_vec) where w_vec is the row-weight vector
    with padding masked to 0.  ``weights`` is None (all-ones), or a width-1
    ChunkSource walked in lockstep (its per-chunk valid counts must match
    the data source's)."""
    if weights is None:
        for chunk, n_valid in source:
            yield chunk, n_valid, _chunk_weights(n_valid, source.chunk_rows, dtype)
        return
    # drive off the DATA iterator: a bare zip would silently drop the
    # data tail if the weight source ran out at a chunk boundary (its
    # n_rows may be unknown before a completed pass, so the up-front
    # row-count check cannot always catch a mismatch)
    wit = iter(weights)
    for chunk, n_valid in source:
        wpair = next(wit, None)
        if wpair is None:
            raise ValueError(
                "sample_weight source ran out of chunks before the data "
                "source — the two must be chunked identically"
            )
        wchunk, wn = wpair
        if wn != n_valid:
            raise ValueError(
                f"sample_weight source yielded {wn} valid rows where the "
                f"data source yielded {n_valid} — the two must be chunked "
                "identically"
            )
        w = np.asarray(wchunk, dtype).reshape(-1)[: source.chunk_rows].copy()
        w[n_valid:] = 0.0
        yield chunk, n_valid, w
    if next(wit, None) is not None:
        raise ValueError(
            "sample_weight source has more chunks than the data source — "
            "the two must be chunked identically"
        )


def _stage_to_device(dtype, stats: PrefetchStats, stage_dtype=None):
    """Stage callable for the prefetch pipeline: pad/convert the host
    chunk and weight vector and issue their device transfers.  Runs in
    the producer thread at depth >= 2 — chunk N+1 stages while chunk N's
    step executes.  The host halves ride along because the k-means||
    loops sample/inspect rows host-side after the device fold.

    ``stage_dtype`` is the DATA chunk's staging dtype — under the bf16
    compute policy (utils/precision.staging_dtype) the cast happens HERE,
    in the producer thread, so the pad/convert output and the
    host->device transfer both carry half the bytes; weights stay at the
    accumulation dtype (they weight f32 accumulators)."""
    stage_dtype = dtype if stage_dtype is None else stage_dtype

    def stage(item):
        chunk, n_valid, w = item
        hc = np.asarray(chunk, stage_dtype)
        hw = np.asarray(w, dtype)
        with stats.transfer():
            cj = jnp.asarray(hc)
            wj = jnp.asarray(hw)
        return chunk, n_valid, w, cj, wj

    return stage


def _staged_chunks(source, weights, dtype, stats: PrefetchStats,
                   stage_dtype=None):
    """Prefetched (host_chunk, n_valid, host_w, dev_chunk, dev_w) stream
    over a (optionally weighted) ChunkSource.  The consumed chunk's
    device buffers retire as the consumer advances (module contract in
    data/prefetch.py).  ``stage_dtype``: see :func:`_stage_to_device`."""
    return Prefetcher(
        _iter_weighted(source, weights, dtype),
        stage=_stage_to_device(dtype, stats, stage_dtype),
        stats=stats,
        retire=True,
    )


# -- multi-host plumbing ----------------------------------------------------
# Each process streams its OWN shard (a per-process ChunkSource); the
# cross-process reductions are host-mediated via process_allgather — the
# DCN analog of the mesh path's ICI psums.  The reduced payloads are tiny
# ((k, d) sums, (d, d) Gram, scalars), so host mediation costs nothing
# next to the per-pass IO, and every process computes bit-identical
# results (deterministic rank-ordered gather + same summation order).


def _world() -> int:
    return jax.process_count()


class _PassGuard:
    """Capture a streaming-source error during a local pass so the next
    cross-process reduction still runs on EVERY rank.

    Without it, a rank whose source raises mid-pass (nondeterministic
    source row-count mismatch, lockstep weight mismatch, IO error) exits
    before its process_allgather while its peers are already blocked
    inside theirs — the world hangs until the distributed timeout.  With
    it, the erroring rank swallows the exception, reaches the reduction,
    and the reduction gathers a 1-byte error flag alongside the data:
    every rank then raises together (the local error is chained on the
    rank that observed it).  Single-process, the original exception is
    re-raised unchanged at the reduction.

    Usage::

        guard = _PassGuard()
        with guard:
            for chunk, n_valid in source: ...accumulate...
        out = _psum_host([...], guard=guard)
    """

    def __init__(self):
        self.err: Exception | None = None

    def __enter__(self) -> "_PassGuard":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None and isinstance(exc, Exception):
            self.err = exc
            return True  # swallowed; the next reduction re-raises on ALL ranks
        return False


def _gather_with_guard(arrays, guard: "_PassGuard | None"):
    """Shared core of _psum_host/_allgather_host: the x64-scoped
    process_allgather, with the guard's error flag riding in front of the
    payload so every rank fails together when any rank's pass failed.
    Returns the per-rank stacked arrays (flag already checked+stripped);
    None signals the single-process identity path (guard re-raised)."""
    if _world() == 1:
        if guard is not None and guard.err is not None:
            raise guard.err
        return None
    from jax.experimental import multihost_utils

    from oap_mllib_tpu.utils.timing import x64_scope

    if guard is not None:
        flag = np.asarray([0 if guard.err is None else 1], np.int64)
        arrays = [flag] + arrays
    # the host-mediated reductions are THE collectives of every streamed
    # multi-process pass: a dead peer surfaces exactly here, so the
    # gather is a fault site (collective.dispatch) and runs under the
    # recovery plane's deadline watchdog (utils/recovery) — a rank that
    # never arrives converts this from a hang into a
    # CollectiveTimeoutError on every survivor
    faults.maybe_fault("collective.dispatch")
    if flightrec.enabled():
        # dispatch fingerprint into the event ring BEFORE the
        # cross-check/gather — the seq a divergence diagnosis or a
        # timeout post-mortem points at (telemetry/flightrec.py)
        flightrec.record(
            "collective", "process_allgather",
            "|".join(str(tuple(np.shape(a))) for a in arrays),
        )
    # collective sanitizer seam: the signature (payload shapes + dtypes)
    # is fingerprinted and cross-checked across ranks before the gather —
    # a rank arriving here with a divergent payload raises on every rank
    # instead of wedging process_allgather (utils/sanitizers.py)
    sanitizers.note_collective(
        "process_allgather", "host",
        tuple(tuple(np.shape(a)) for a in arrays),
        ",".join(str(getattr(a, "dtype", "?")) for a in arrays),
    )
    with x64_scope(True):
        gathered = recovery.guarded_dispatch(
            "process_allgather", "host",
            lambda: multihost_utils.process_allgather(arrays),
        )
    if guard is not None:
        if int(np.asarray(gathered[0]).sum()) > 0:
            raise RuntimeError(
                "streamed pass failed on at least one process"
            ) from guard.err
        gathered = gathered[1:]
    return [np.asarray(g) for g in gathered]


def _materialize(arrays, guard: "_PassGuard | None"):
    """Fetch accumulators to host np arrays, under the guard: the
    np.asarray of an async device computation is where a rank-local XLA
    error (e.g. RESOURCE_EXHAUSTED mid-fit on one host) surfaces, and it
    must reach the collective like a source error — not strand peers in
    process_allgather.  On a failed fetch the payload is replaced by
    zeros of the same shapes (rank-consistent gather payloads are a
    collective requirement; the riding error flag aborts the world
    before anyone consumes them)."""
    if guard is not None:
        with guard:
            return [np.asarray(a) for a in arrays]
        return [
            np.zeros(np.shape(a), getattr(a, "dtype", np.float64))
            for a in arrays
        ]
    return [np.asarray(a) for a in arrays]


def _ring_mesh():
    """The device mesh for the streamed ring reduction, or None when the
    psum/host path must run: multi-process world, pure data-parallel
    mesh (a model axis would misalign the one-slot-per-device stacking),
    and Config.ring_reduction armed with >= 2 devices on the data axis
    (kmeans_ops.ring_enabled — the shared fallback contract)."""
    if _world() == 1:
        return None
    from oap_mllib_tpu.config import get_config

    cfg = get_config()
    if cfg.model_parallel != 1:
        return None
    from oap_mllib_tpu.ops.kmeans_ops import ring_enabled
    from oap_mllib_tpu.parallel.mesh import get_mesh

    mesh = get_mesh()
    if not ring_enabled(mesh, cfg.data_axis, cfg):
        return None
    return mesh


def _ring_reduce_f32(arrays, mesh, axis: str):
    """Sum a list of f32 host arrays across processes through ONE packed
    ring reduction (ops/pallas/ring_reduce): the payloads flatten into a
    (D, ceil(total/D)) segment sheet — each ring segment is a real chunk
    of the moments — ride a one-slot-per-device stacked array onto the
    mesh, and come back fully summed on every slot.  This is the
    streamed multi-host half of the ISSUE 9 ring plane: the per-pass
    centroid/Gram moments stop paying a standalone host-mediated
    allgather serialized behind the pass."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oap_mllib_tpu.ops.pallas.ring_reduce import stacked_ring_fn

    d_ax = mesh.shape[axis]
    flat = np.concatenate(
        [np.asarray(a, np.float32).ravel() for a in arrays]
    )
    total = flat.size
    cols = max(1, -(-total // d_ax))
    buf = np.zeros((d_ax, cols), np.float32)
    buf.ravel()[:total] = flat
    if flightrec.enabled():
        flightrec.record(
            "collective", "ring_allreduce", f"{axis}|({d_ax},{cols})"
        )
    sanitizers.note_collective(
        "ring_allreduce", axis, (d_ax, cols), "float32"
    )
    n_slots = d_ax // max(1, jax.process_count())
    local = np.zeros((n_slots, d_ax, cols), np.float32)
    local[0] = buf  # this process's payload in its first device slot
    sharding = NamedSharding(mesh, P(axis, None, None))
    stacked = jax.make_array_from_process_local_data(sharding, local)
    # segmented-start epilogue geometry (ops/pallas/autotune): resolved
    # from config + cache only — a pure function of (config, bucket) on
    # every rank, so the ring program stays rank-uniform (R16; mode "on"
    # under a multi-process world resolves "default-multiproc" for the
    # same reason)
    from oap_mllib_tpu.ops.pallas import autotune

    segments = autotune.resolve(
        "ring", autotune.shape_bucket(d_ax, cols)
    )["segments"]
    out = stacked_ring_fn(mesh, axis, segments=segments)(stacked)
    summed = np.asarray(out.addressable_shards[0].data)[0].ravel()[:total]
    res, off = [], 0
    for a in arrays:
        n = int(np.asarray(a).size)
        res.append(
            summed[off : off + n].reshape(np.shape(a)).astype(a.dtype)
        )
        off += n
    return res


def _psum_host(arrays, guard: "_PassGuard | None" = None):
    """Sum each array across processes; identity single-process.  Returns
    np arrays, identical on every process.  The gather runs under an x64
    scope: process_allgather device_puts its payload, which would
    silently demote f64/i64 (row counts, reservoir state) when the
    session default is x64-off.  ``guard``: see _PassGuard — when given,
    an error flag rides the gather and all ranks fail together.

    With the ring plane armed (:func:`_ring_mesh`), the f32 moment
    payloads reduce through ONE packed device ring instead of the
    host-mediated allgather; the error flag and any non-f32 payloads
    (row counts, reservoir state) keep the host gather, which runs FIRST
    so a failed rank still aborts every peer before the ring launches —
    the route decision is a pure function of dtypes, so every rank
    issues the same collective sequence."""
    arrays = _materialize(arrays, guard)
    if _world() == 1:
        if guard is not None and guard.err is not None:
            raise guard.err
        return arrays
    mesh = _ring_mesh()
    f32_idx = [
        i for i, a in enumerate(arrays)
        if np.asarray(a).dtype == np.float32
    ]
    if mesh is None or not f32_idx:
        gathered = _gather_with_guard(arrays, guard)
        return [g.sum(axis=0) for g in gathered]
    from oap_mllib_tpu.config import get_config

    rest_idx = [i for i in range(len(arrays)) if i not in f32_idx]
    gathered_rest = (
        _gather_with_guard([arrays[i] for i in rest_idx], guard)
        if rest_idx or guard is not None
        else []
    )
    ringed = _ring_reduce_f32(
        [arrays[i] for i in f32_idx], mesh, get_config().data_axis
    )
    out: list = [None] * len(arrays)
    for j, i in enumerate(f32_idx):
        out[i] = ringed[j]
    for j, i in enumerate(rest_idx):
        out[i] = gathered_rest[j].sum(axis=0)
    return out


def _allgather_host(arrays, guard: "_PassGuard | None" = None):
    """Gather each array across processes along a new leading (rank)
    axis; adds the axis single-process too (shape-stable callers).
    x64 scope and ``guard``: see _psum_host."""
    arrays = _materialize(arrays, guard)
    gathered = _gather_with_guard(arrays, guard)
    if gathered is None:
        return [a[None] for a in arrays]
    return gathered


def _fleet_pass(phase: str, stats: PrefetchStats, pass_wall_s: float,
                timings=None) -> None:
    """Fleet rollup seam (telemetry/fleet.py, ISSUE 11): after a pass's
    reduction succeeded on every rank, allgather one FIXED-shape
    per-rank stat frame over the same host-collective plane (so the
    rollup inherits the deadline watchdog and the collective
    sanitizer's fingerprinting) and fold it into the ``oap_fleet_*``
    metrics + the per-fit fleet window.  Disarmed
    (``Config.fleet_stats``) this is one config check; armed, the
    decision is a pure function of (config, world) so every rank
    issues the identical extra collective.

    The straggler controller (parallel/balance.py, ISSUE 15) rides the
    SAME gathered frames — every rank holds identical data, so every
    rank computes the identical re-plan with no additional collective."""
    if not fleet.armed(_world()):
        return
    elapsed = tick()
    frame = fleet.local_frame(stats, pass_wall_s)
    (gathered,) = _allgather_host([frame])
    fleet.fold_pass(phase, gathered)
    from oap_mllib_tpu.parallel import balance

    balance.observe_pass(phase, gathered)
    if timings is not None:
        timings.add("fleet", elapsed())


def capability_sync(frame: np.ndarray) -> np.ndarray:
    """Fit-start capability gather (parallel/balance.py, ISSUE 15): one
    fixed-shape allgather of each rank's ``[capability, origin, hbm,
    host]`` frame over the sanctioned host-collective seam — it
    inherits the deadline watchdog, the collective sanitizer's
    fingerprinting, and the fault site like every other host
    collective.  Called once per (process, world size); balance caches
    the fold.  Returns the gathered ``(world, 4)`` frames, identical on
    every rank."""
    (gathered,) = _allgather_host([np.asarray(frame, np.float64)])
    return gathered


def _checked_entry(validate) -> None:
    """Run entry validation under a guard and sync the outcome across
    ranks (one tiny scalar gather).  Without this, a rank whose
    validation fails (e.g. a malformed per-rank weight shard) raises
    before its first collective while peers with consistent shards
    proceed into the pass and hang in process_allgather.

    Callers skip this entirely for statically-infallible validations
    (sample_weight=None) — the sync only pays for itself when the
    validator can actually raise, and None-ness is assumed consistent
    across ranks (passing a weight source on some ranks only is API
    misuse outside this contract)."""
    guard = _PassGuard()
    with guard:
        validate()
    _psum_host([np.zeros((), np.int64)], guard=guard)


# ---------------------------------------------------------------------------
# K-Means
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("precision", "need_cost", "policy"),
    donate_argnums=(0, 1, 2),
)
def _kmeans_chunk_accum(sums, counts, cost, chunk, w, centers, precision,
                        need_cost, policy="f32"):
    s, c, t = kmeans_ops._accumulate(
        chunk, w, centers, precision, need_cost, policy
    )
    return sums + s, counts + c, cost + t


def _check_weight_source(source: ChunkSource, weights) -> None:
    if weights is None:
        return
    if not isinstance(weights, ChunkSource):
        raise TypeError("sample_weight for a streamed fit must be a ChunkSource")
    if weights.n_features != 1:
        raise ValueError("sample_weight source must have width 1")
    if weights.chunk_rows != source.chunk_rows:
        raise ValueError(
            f"sample_weight chunk_rows {weights.chunk_rows} != data "
            f"chunk_rows {source.chunk_rows}"
        )
    if (
        weights.n_rows is not None
        and source.n_rows is not None
        and weights.n_rows != source.n_rows
    ):
        raise ValueError(
            f"sample_weight rows {weights.n_rows} != data rows {source.n_rows}"
        )


def streamed_accumulate(
    source: ChunkSource, centers, dtype, precision: str, need_cost: bool,
    weights=None, timings=None, phase: str = "lloyd_loop",
    policy: str = "f32",
):
    """One full assignment pass over this process's shard, reduced across
    processes: (sums (k,d), counts (k,), cost) as host arrays (identical
    on every process).  Chunks arrive through the prefetch pipeline —
    chunk N+1 stages/transfers while chunk N's accumulate executes; the
    pass's stage/transfer/compute split lands in ``timings`` under
    ``phase`` when given.  Under the bf16 ``policy`` chunks stage at
    bfloat16 (half the transfer bytes); accumulators stay ``dtype``."""
    k, d = centers.shape
    stage_dtype = psn.staging_dtype(policy, dtype)
    sums = jnp.zeros((k, d), dtype)
    counts = jnp.zeros((k,), dtype)
    cost = jnp.zeros((), dtype)
    stats = PrefetchStats()
    # one key per pass (shapes are static across chunks): the per-chunk
    # program registers with the program-cache registry — record_execute
    # off, the device time is already the prefetch ``compute`` split
    step_key = (
        progcache.backend_fingerprint(),
        (source.chunk_rows, d, k), str(np.dtype(dtype)),
        str(stage_dtype), precision, need_cost, policy,
    )
    elapsed = tick()
    guard = _PassGuard()
    with guard:
        with _staged_chunks(
            source, weights, dtype, stats, stage_dtype
        ) as pf:
            for _, _, _, cj, wj in pf:
                with progcache.launch(
                    "kmeans.stream_accum", step_key, timings, phase,
                    record_execute=False,
                ):
                    sums, counts, cost = _kmeans_chunk_accum(
                        sums, counts, cost, cj, wj, centers, precision,
                        need_cost, policy,
                    )
    pass_wall = elapsed()
    stats.finalize(timings, phase, pass_wall)
    out = _psum_host([sums, counts, cost], guard=guard)
    _fleet_pass(phase, stats, pass_wall, timings)
    return out


@jax.jit
def _center_update(centers, sums, counts):
    safe = counts[:, None] > 0
    new_centers = jnp.where(safe, sums / jnp.maximum(counts[:, None], 1e-30), centers)
    moved_sq = jnp.sum((new_centers - centers) ** 2, axis=1)
    return new_centers, jnp.max(moved_sq)


def lloyd_run_streamed(
    source: ChunkSource, init_centers: np.ndarray, max_iter: int, tol: float,
    dtype, precision: str = "highest", weights=None, validated: bool = False,
    timings=None, policy: str = "f32", checkpoint=None, resume=None,
):
    """Streamed Lloyd loop; same return contract as kmeans_ops.lloyd_run:
    (centers, n_iter, cost, counts).  Convergence semantics match
    _lloyd_loop (every center's squared move <= tol^2, or max_iter); one
    host sync per iteration (the converged flag) instead of zero — the
    price of host-driven passes.  ``weights`` is an optional width-1
    ChunkSource walked in lockstep (per-row weights); ``validated``
    skips the entry validation + its cross-rank sync when the caller
    (KMeans._fit_source) already ran it — the sync is one collective per
    call and must not triple up inside a single fit.  ``timings``
    accumulates the per-pass stage/transfer/compute split under
    ``lloyd_loop/``.

    ``checkpoint``/``resume`` (utils/checkpoint.py): the elastic-worlds
    channel.  ``resume`` is a restored :class:`RestoreResult` whose
    centroids the CALLER already used as ``init_centers`` (skipping the
    init passes); here it re-enters the loop at the recorded pass.
    ``checkpoint`` writes the post-pass centroids + pass index + the
    converged flag every ``Config.checkpoint_interval`` passes.  The
    pass math is untouched, so continuation is bit-identical in an
    unchanged world; a changed world only reorders the cross-rank
    reduction sums (<= fp tolerance)."""
    if weights is not None and not validated:
        _checked_entry(lambda: _check_weight_source(source, weights))
    from oap_mllib_tpu.utils.resilience import check_finite

    centers = jnp.asarray(np.asarray(init_centers, dtype))
    tol_sq = float(tol) ** 2
    n_iter = 0
    converged = False
    if resume is not None and resume.found:
        n_iter = min(int(resume.step), max_iter)
        converged = bool(resume.extra.get("converged", False))
    while n_iter < max_iter and not converged:
        sums, counts, _ = streamed_accumulate(
            source, centers, dtype, precision, need_cost=False,
            weights=weights, timings=timings, policy=policy,
        )
        centers, max_moved = _center_update(centers, sums, counts)
        n_iter += 1
        # iterate-level guardrail (Config.nonfinite_policy): a NaN/Inf
        # centroid poisons every later pass silently — catch it at the
        # iteration that produced it, while the cause is still nearby
        check_finite(centers, f"K-Means centroids (streamed pass {n_iter})")
        converged = float(max_moved) <= tol_sq
        if checkpoint is not None:
            checkpoint.maybe_write(
                n_iter, {"centers": np.asarray(centers)},
                extra={"converged": converged}, force=converged,
            )
    # final cost/counts pass: full precision INPUTS too (policy="f32" —
    # one extra f32-staged pass).  The cost identity |x|^2 + |c|^2 - 2x.c
    # cancels catastrophically for tight clusters under bf16-rounded
    # inputs (measured ~2x cost inflation where centroids matched to
    # 1e-4): the user-facing objective must not carry the fast policy's
    # rounding — the same contract as the in-memory _lloyd_run_jit,
    # which recomputes against its f32 table
    _, counts, cost = streamed_accumulate(
        source, centers, dtype, "highest", need_cost=True, weights=weights,
        timings=timings, policy="f32",
    )
    return centers, n_iter, cost, counts


# ---------------------------------------------------------------------------
# K-Means init
# ---------------------------------------------------------------------------


def reservoir_sample(
    source: ChunkSource, k: int, seed: int, timings=None,
) -> np.ndarray:
    """Uniform k-row sample in one pass (Algorithm R, vectorized per chunk:
    one rng draw per chunk and a Python loop only over the expected
    O(k log(n/k)) reservoir hits, never over all n rows).  The source is
    prefetched with an identity stage — no device transfer here, but the
    background pull overlaps file IO with the host reservoir updates.

    Multi-process: each process reservoirs its own shard, then the
    per-process reservoirs are merged by weighted sampling without
    replacement (Efraimidis–Spirakis keys; each reservoir row represents
    seen_p / |reservoir_p| rows of the global table).  Deterministic rank
    -ordered gather + a shared seed make every process return the SAME
    sample."""
    rng = np.random.default_rng(seed)
    sample: List[np.ndarray] = []
    seen = 0
    stats = PrefetchStats()
    elapsed = tick()
    guard = _PassGuard()
    with guard, Prefetcher(source, stats=stats) as pf:
        for chunk, n_valid in pf:
            start = 0
            if len(sample) < k:  # head-fill straight into the reservoir
                take = min(k - len(sample), n_valid)
                sample.extend(chunk[i].copy() for i in range(take))
                start = take
            if start < n_valid:
                # row at global index g replaces slot j ~ U[0, g] iff j < k
                highs = np.arange(seen + start + 1, seen + n_valid + 1)
                j = rng.integers(0, highs)  # vectorized per-row draws
                for i in np.nonzero(j < k)[0]:  # sparse hits only
                    sample[j[i]] = chunk[start + i].copy()
            seen += n_valid
    stats.finalize(timings, "init_centers", elapsed())
    if guard.err is not None and _world() == 1:
        raise guard.err
    if _world() > 1:
        d = source.n_features
        local = np.zeros((k, d))
        if sample:
            local[: len(sample)] = np.stack(sample)
        rows_g, nv_g, seen_g = _allgather_host(
            [local, np.asarray([len(sample)]), np.asarray([seen])],
            guard=guard,
        )
        rows = rows_g.reshape(-1, d)  # (nproc*k, d), rank-major
        nv = nv_g.ravel()
        weights = np.zeros(len(rows))
        for p in range(len(nv)):
            if nv[p]:
                weights[p * k : p * k + nv[p]] = seen_g.ravel()[p] / nv[p]
        valid = weights > 0
        if not valid.any():
            raise ValueError("empty source (all processes)")
        # Efraimidis–Spirakis: top-k keys u^(1/w) ~ weighted sample
        # without replacement; same rng stream on every process
        merge_rng = np.random.default_rng(seed + 1000003)
        keys = np.where(
            valid, merge_rng.random(len(rows)) ** (1.0 / np.maximum(weights, 1e-300)), -1.0
        )
        top = np.argsort(-keys, kind="stable")[: min(k, int(valid.sum()))]
        sample = [rows[t] for t in top]
        seen = int(seen_g.sum())
    if not sample:
        raise ValueError("empty source")
    while len(sample) < k:  # fewer rows than clusters: duplicate
        sample.append(sample[len(sample) % max(1, seen)])
    return np.stack(sample)


@functools.partial(jax.jit, static_argnames=("precision",))
def _chunk_min_d2(chunk, dmin, cands, precision="highest"):
    """Fold candidate distances into the chunk's running min."""
    d2 = kmeans_ops.pairwise_sq_dists(chunk, cands, precision)
    return jnp.minimum(dmin, jnp.min(d2, axis=1))


@jax.jit
def _chunk_ownership(chunk, w, cands):
    """(n_cand,) row weight owned by each candidate (segment-sum)."""
    d2 = kmeans_ops.pairwise_sq_dists(chunk, cands)
    owner = kmeans_ops.argmin_rows(d2)
    return jnp.zeros((cands.shape[0],), w.dtype).at[owner].add(w)


def _pad_cands(cands: np.ndarray, cap: int, d: int) -> np.ndarray:
    """Pad candidate blocks to a static cap with far-away dummies (1e15)
    so per-round shapes stay constant and the fold compiles once."""
    out = np.full((cap, d), 1e15, np.float64)
    if len(cands):
        out[: len(cands)] = cands
    return out


def init_kmeans_parallel_streamed(
    source: ChunkSource, k: int, seed: int, init_steps: int, dtype,
    weights=None, validated: bool = False, timings=None,
    policy: str = "f32",
) -> np.ndarray:
    """Streamed k-means|| (Bahmani), host-orchestrated.

    Differences vs the in-memory device version (kmeans_ops
    .init_kmeans_parallel): the per-row min-distance state lives on host
    (one f32 per row — 400 MB at 100M rows, far under host RAM), and each
    sampling round uses the cost total from the previous pass (one-round
    -stale phi; the l=2k oversampling absorbs the drift — parity tests
    compare converged cost, not centers, survey §7.3).

    Multi-process: each process folds/samples its own shard; phi, the
    per-round picks, and the ownership weights are reduced/gathered across
    processes, so every process ends each round with the SAME candidate
    set (the sampling rng is per-process — distinct shards — while the
    final weighted k-means++ key is shared).

    ``weights``: optional width-1 ChunkSource of per-row weights, walked
    in lockstep — they scale the sampling cost (phi = sum w*dmin, like
    the in-memory version's weighted _pll_round) and the candidate
    ownership.  ``validated``: see lloyd_run_streamed.  Every pass pulls
    through the prefetch pipeline (chunk staging overlaps the device
    distance fold); per-chunk dmin state stays consumer-side — it is
    final only for chunks the consumer already passed, so the producer
    must not read it ahead."""
    if weights is not None and not validated:
        _checked_entry(lambda: _check_weight_source(source, weights))
    d = source.n_features
    l = 2.0 * k
    cap = 4 * k  # per-round candidate block (2x expected picks)
    # bf16 policy: chunks stage at bfloat16 for the distance folds (the
    # candidate ROWS are picked from the untouched host chunks, so the
    # candidates themselves keep full precision; only the sampling
    # probabilities and ownership weights carry bf16 rounding — Bahmani
    # oversampling is robust to far larger perturbations, and parity
    # compares converged cost, survey §7.3)
    stage_dtype = psn.staging_dtype(policy, dtype)
    # per-process stream for sampling OWN rows (the final reduction's key
    # is shared: it must be identical on every process)
    samp_rng = np.random.default_rng(seed + 31 * jax.process_index())

    c0 = reservoir_sample(source, 1, seed, timings=timings)
    cands = [c0[0]]
    new_block: np.ndarray = _pad_cands(c0, cap, d)  # picks awaiting dmin fold

    # One pass per round: fold the PREVIOUS round's picks into dmin while
    # sampling this round's with the previous pass's phi (the one-round
    # -stale phi of the docstring).  Round 0 is the distance-init pass —
    # it folds c0 and records phi without sampling.
    dmin_chunks: List[np.ndarray] = []
    phi = 0.0
    for rnd in range(init_steps + 1):
        sampling = rnd > 0
        if sampling and phi <= 0.0:
            break
        cands_dev = (
            jnp.asarray(new_block.astype(dtype)) if len(new_block) else None
        )
        picks: List[np.ndarray] = []
        new_phi = 0.0
        stats = PrefetchStats()
        elapsed = tick()
        guard = _PassGuard()
        with guard, _staged_chunks(
            source, weights, dtype, stats, stage_dtype
        ) as pf:
            for ci, (chunk, n_valid, wv, cj, _) in enumerate(pf):
                if cands_dev is not None:
                    progcache.note(
                        "kmeans.stream_pll_fold",
                        (progcache.backend_fingerprint(),
                         progcache.array_key(cj, cands_dev)),
                    )
                    # the d2 cache is host-resident by design (device
                    # chunks retire); staging the previous round's dmin
                    # up and fetching the fold back are ONE audited
                    # consume step — allow_transfers is the runtime
                    # analog of the lint suppression
                    with sanitizers.allow_transfers():
                        prev = (
                            jnp.asarray(dmin_chunks[ci])
                            if rnd > 0
                            else jnp.full(
                                (source.chunk_rows,), np.inf, dtype)
                        )
                        # oaplint: disable=stream-host-sync -- host d2 cache is the consume step
                        h = np.array(_chunk_min_d2(cj, prev, cands_dev))
                    h[n_valid:] = 0.0  # padded rows carry no cost
                    if rnd > 0:
                        dmin_chunks[ci] = h
                    else:
                        dmin_chunks.append(h)
                else:
                    h = dmin_chunks[ci]
                hw = h * wv  # weighted cost (all-ones when weights is None)
                new_phi += float(hw.sum())
                if sampling:
                    prob = np.minimum(l * hw / max(phi, 1e-300), 1.0)
                    hit = samp_rng.random(source.chunk_rows) < prob
                    hit[n_valid:] = False
                    for i in np.nonzero(hit)[0]:
                        picks.append(chunk[i].copy())
        stats.finalize(timings, "init_centers", elapsed())
        (phi_arr,) = _psum_host([np.asarray([new_phi])], guard=guard)
        phi = float(phi_arr[0])
        if _world() > 1:
            # fixed-shape gather of each process's picks (rank-major, so
            # every process extends cands identically); overflow beyond
            # cap drops, like the in-memory slot buffer
            local = np.zeros((cap, d))
            n_local = min(len(picks), cap)
            if n_local:
                local[:n_local] = np.stack(picks[:n_local])
            rows_g, cnt_g = _allgather_host([local, np.asarray([n_local])])
            picks = [
                rows_g[p, i]
                for p in range(rows_g.shape[0])
                for i in range(int(cnt_g.ravel()[p]))
            ]
        cands.extend(picks)
        new_block = (
            _pad_cands(
                np.stack(picks), cap * ((len(picks) + cap - 1) // cap), d
            )
            if picks
            else np.zeros((0, d))
        )

    cand_arr = np.stack(cands)
    if cand_arr.shape[0] <= k:
        extra = reservoir_sample(
            source, k - cand_arr.shape[0] + 1, seed + 1, timings=timings
        )
        return np.concatenate([cand_arr, extra], axis=0)[:k]

    # ownership pass: weight candidates
    cands_dev = jnp.asarray(cand_arr.astype(dtype))
    own = np.zeros((cand_arr.shape[0],), np.float64)
    stats = PrefetchStats()
    elapsed = tick()
    guard = _PassGuard()
    with guard, _staged_chunks(
        source, weights, dtype, stats, stage_dtype
    ) as pf:
        for _, _, _, cj, wj in pf:
            progcache.note(
                "kmeans.stream_pll_own",
                (progcache.backend_fingerprint(),
                 progcache.array_key(cj, cands_dev)),
            )
            with sanitizers.allow_transfers():  # audited host accumulation
                # oaplint: disable=stream-host-sync -- ownership sums accumulate on host by design
                own += np.asarray(_chunk_ownership(cj, wj, cands_dev))
    stats.finalize(timings, "init_centers", elapsed())
    (own,) = _psum_host([own], guard=guard)
    # the weighted k-means++ reduction, on the device like the in-memory
    # route's: slots in whole blocks of ``cap`` (the picks of a round are
    # not capped in one process, and a buffer shaped by their count would
    # be a program a fit), the padding marked invalid
    n_cand = cand_arr.shape[0]
    slots = np.zeros((cap * -(-n_cand // cap), d), dtype)
    slots[:n_cand] = cand_arr
    cand_w = np.zeros((slots.shape[0],), dtype)
    cand_w[:n_cand] = own
    return kmeans_ops.reduce_candidates(
        slots, cand_w, np.arange(slots.shape[0]) < n_cand,
        jax.random.fold_in(jax.random.PRNGKey(seed), 7777), k,
    )


# ---------------------------------------------------------------------------
# PCA
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def _colsum_chunk(total, chunk, w):
    return total + jnp.sum(psn.upcast(chunk) * w[:, None], axis=0)


@functools.partial(
    jax.jit, static_argnames=("precision", "policy"), donate_argnums=(0,)
)
def _gram_chunk(gram, chunk, w, mean, precision, policy="f32"):
    xc = (psn.upcast(chunk) - mean[None, :]) * w[:, None]
    return gram + psn.pdot(xc.T, xc, policy, precision)


# Kahan/Neumaier-compensated accumulators for the reduced-precision
# policies: the per-chunk partials carry bf16 input rounding already, so
# the CROSS-PASS f32 accumulation must not add O(n_chunks * eps)
# cancellation on top — the compensation term recovers the bits each
# f32 += loses, keeping the summation error bounded independent of the
# chunk count (the "f32 accumulators with compensated summation across
# passes" half of the policy contract).  Not used by the f32 policy:
# its accumulation order must stay bit-identical to the pre-policy code.


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _colsum_chunk_comp(total, comp, chunk, w):
    s = jnp.sum(psn.upcast(chunk) * w[:, None], axis=0)
    y = s - comp
    t = total + y
    comp = (t - total) - y
    return t, comp


@functools.partial(
    jax.jit, static_argnames=("precision", "policy"), donate_argnums=(0, 1)
)
def _gram_chunk_comp(gram, comp, chunk, w, mean, precision, policy):
    xc = (psn.upcast(chunk) - mean[None, :]) * w[:, None]
    g = psn.pdot(xc.T, xc, policy, precision)
    y = g - comp
    t = gram + y
    comp = (t - gram) - y
    return t, comp


# -- fused-kernel per-chunk accumulators (ops/pallas/pca_kernel) ------------
# Same accumulation structure as the XLA chunk fns above, with the
# center+mask+Gram (and the colsum reduction) fused into one Pallas
# program per chunk — no HBM-materialized centered temp.  Dispatch is
# pca_ops.use_pallas_gram (TPU + single device + f32); the ``interpret``
# static exists so tier-1 can exercise the kernels on CPU.


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_rows", "depth"),
    donate_argnums=(0,),
)
def _colsum_chunk_pallas(total, chunk, w, interpret=False, tile_rows=None,
                         depth=None):
    from oap_mllib_tpu.ops.pallas import pca_kernel as _pk

    _, cs, _ = _pk.moments_traced(
        chunk, w, jnp.zeros((chunk.shape[1],), jnp.float32),
        "highest", interpret, False, tile_rows, depth,
    )
    return total + cs


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_rows", "depth"),
    donate_argnums=(0, 1),
)
def _colsum_chunk_pallas_comp(total, comp, chunk, w, interpret=False,
                              tile_rows=None, depth=None):
    from oap_mllib_tpu.ops.pallas import pca_kernel as _pk

    _, s, _ = _pk.moments_traced(
        chunk, w, jnp.zeros((chunk.shape[1],), jnp.float32),
        "highest", interpret, False, tile_rows, depth,
    )
    y = s - comp
    t = total + y
    comp = (t - total) - y
    return t, comp


@functools.partial(
    jax.jit, static_argnames=("mode", "interpret", "tile_rows", "depth"),
    donate_argnums=(0,),
)
def _gram_chunk_pallas(gram, chunk, w, mean, mode, interpret=False,
                       tile_rows=None, depth=None):
    from oap_mllib_tpu.ops.pallas import pca_kernel as _pk

    g, _, _ = _pk.moments_traced(
        chunk, w, mean, mode, interpret, True, tile_rows, depth
    )
    return gram + g


@functools.partial(
    jax.jit, static_argnames=("mode", "interpret", "tile_rows", "depth"),
    donate_argnums=(0, 1),
)
def _gram_chunk_pallas_comp(gram, comp, chunk, w, mean, mode,
                            interpret=False, tile_rows=None, depth=None):
    from oap_mllib_tpu.ops.pallas import pca_kernel as _pk

    g, _, _ = _pk.moments_traced(
        chunk, w, mean, mode, interpret, True, tile_rows, depth
    )
    y = g - comp
    t = gram + y
    comp = (t - gram) - y
    return t, comp


def covariance_streamed(
    source: ChunkSource, dtype, precision: str = "highest", timings=None,
    policy: str = "f32", checkpoint=None,
):
    """Two-pass streamed covariance: (cov (d,d), mean (d,), n_rows), as
    host arrays identical on every process.

    Pass 1 accumulates column sums (mean), pass 2 the mean-centered Gram —
    identical numerics to ops.pca_ops.covariance, O(chunk) device memory;
    multi-process shards reduce across processes after each pass.  Both
    passes pull through the prefetch pipeline; the split lands in
    ``timings`` under ``covariance_streamed/``.

    ``policy`` (utils/precision.py): bf16 stages chunks at bfloat16
    (half the transfer bytes), runs the per-chunk Gram matmuls on bf16
    operands with f32 accumulation, and compensates the cross-chunk f32
    accumulation (Kahan) so the pass count cannot amplify the rounding;
    f32 keeps the exact pre-policy accumulators.

    ``checkpoint`` (utils/checkpoint.py): PCA's iterate state is its
    pass structure — after the colsum pass the reduced column sums + row
    count checkpoint (the streamed accumulators of the tentpole), so a
    preempted fit resumes straight into the Gram pass.  The reduced
    moments are identical on every rank, so restore is world-size-
    independent by construction.
    """
    d = source.n_features
    stage_dtype = psn.staging_dtype(policy, dtype)
    compensated = policy == "bf16"
    from oap_mllib_tpu.config import get_config
    from oap_mllib_tpu.ops import pca_ops
    from oap_mllib_tpu.utils.resilience import check_finite

    # fused-kernel route (ops/pallas/pca_kernel): same per-chunk
    # accumulation at the kernel tier, one Pallas program per chunk —
    # validated on EVERY streamed fit so a typo'd pca_kernel raises here
    use_pk = pca_ops.use_pallas_gram(
        get_config().pca_kernel, d, precision, dtype
    )
    # tuned kernel geometry, resolved ONCE per pass pair outside the
    # chunk loop (the chunk fns take it as jit statics); default path
    # keeps (None, None) = the hand-picked constants
    pk_rows = pk_depth = None
    if use_pk:
        from oap_mllib_tpu.ops.pallas import autotune

        geo = autotune.resolve("pca", autotune.shape_bucket(d), precision)
        pk_rows, pk_depth = geo["tile_rows"], geo["depth"]

    resume = checkpoint.restore() if checkpoint is not None else None
    base_key = (
        progcache.backend_fingerprint(),
        (source.chunk_rows, d), str(np.dtype(dtype)), str(stage_dtype),
        precision, policy, pk_rows, pk_depth,
    )
    if resume is not None and resume.found and (
            resume.extra.get("stage") == "colsum"):
        total = resume.arrays["colsum"]
        n = int(resume.extra["n_rows"])
    else:
        total = jnp.zeros((d,), dtype)
        comp = jnp.zeros((d,), dtype)
        n = 0
        stats = PrefetchStats()
        elapsed = tick()
        guard = _PassGuard()
        with guard, _staged_chunks(
            source, None, dtype, stats, stage_dtype
        ) as pf:
            for _, n_valid, _, cj, wj in pf:
                with progcache.launch(
                    "pca.stream_colsum", base_key, timings,
                    "covariance_streamed", record_execute=False,
                ):
                    if use_pk and compensated:
                        total, comp = _colsum_chunk_pallas_comp(
                            total, comp, cj, wj,
                            tile_rows=pk_rows, depth=pk_depth,
                        )
                    elif use_pk:
                        total = _colsum_chunk_pallas(
                            total, cj, wj, tile_rows=pk_rows, depth=pk_depth
                        )
                    elif compensated:
                        total, comp = _colsum_chunk_comp(total, comp, cj, wj)
                    else:
                        total = _colsum_chunk(total, cj, wj)
                n += n_valid
        pass_wall = elapsed()
        stats.finalize(timings, "covariance_streamed", pass_wall)
        total, n_arr = _psum_host(
            [total, np.asarray([n], np.int64)], guard=guard
        )
        _fleet_pass("covariance_streamed", stats, pass_wall, timings)
        # per-pass guardrails (Config.nonfinite_policy): an overflowed
        # f32 column sum or Gram silently yields Inf/NaN eigenvectors
        # passes later
        check_finite(total, "PCA column sums (streamed mean pass)")
        n = int(n_arr[0])
        if checkpoint is not None:
            checkpoint.maybe_write(
                1, {"colsum": np.asarray(total)},
                extra={"stage": "colsum", "n_rows": n}, force=True,
            )
    if n < 1:
        raise ValueError("empty source")
    mean = jnp.asarray(total.astype(dtype) / n)
    gram = jnp.zeros((d, d), dtype)
    gcomp = jnp.zeros((d, d), dtype)
    stats = PrefetchStats()
    elapsed = tick()
    guard = _PassGuard()
    with guard, _staged_chunks(source, None, dtype, stats, stage_dtype) as pf:
        for _, _, _, cj, wj in pf:
            with progcache.launch(
                "pca.stream_gram", base_key, timings,
                "covariance_streamed", record_execute=False,
            ):
                if use_pk and compensated:
                    gram, gcomp = _gram_chunk_pallas_comp(
                        gram, gcomp, cj, wj, mean, precision,
                        tile_rows=pk_rows, depth=pk_depth,
                    )
                elif use_pk:
                    gram = _gram_chunk_pallas(
                        gram, cj, wj, mean, precision,
                        tile_rows=pk_rows, depth=pk_depth,
                    )
                elif compensated:
                    gram, gcomp = _gram_chunk_comp(
                        gram, gcomp, cj, wj, mean, precision, policy
                    )
                else:
                    gram = _gram_chunk(gram, cj, wj, mean, precision, policy)
    pass_wall = elapsed()
    stats.finalize(timings, "covariance_streamed", pass_wall)
    (gram,) = _psum_host([gram], guard=guard)
    _fleet_pass("covariance_streamed", stats, pass_wall, timings)
    check_finite(gram, "PCA Gram accumulator (streamed Gram pass)")
    cov = gram.astype(np.float64 if dtype == np.float64 else np.float32)
    cov = cov / max(n - 1.0, 1.0)
    cov = 0.5 * (cov + cov.T)
    return cov, np.asarray(mean), n
