"""Unified configuration system.

The reference scatters configuration across five channels (survey §5): Spark
conf keys under ``spark.oap.mllib.*`` (e.g. ``spark.oap.mllib.oneccl.kvs.ip`` /
``.port``, reference KMeansDALImpl.scala:40-44), Spark resource settings
(``spark.executor.cores``, Utils.scala:51-58), env vars set from code
(``CCL_ATL_TRANSPORT``, OneCCL.scala:26-30), build-time env, and a deployment
env script.  This framework unifies them into one dataclass with env-var
overrides under the ``OAP_MLLIB_TPU_*`` namespace (the ``spark.oap.mllib.*``
analog) plus a programmatic API.

Env mapping: config field ``foo_bar`` <- env ``OAP_MLLIB_TPU_FOO_BAR``.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Optional

_ENV_PREFIX = "OAP_MLLIB_TPU_"


def _env_bool(val: str) -> bool:
    return val.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class Config:
    """Global framework configuration.

    Fields mirror the reference's config surface:

    - ``device``: backend selector, the ``spark.oap.mllib.device=tpu`` analog
      (BASELINE.json north star). One of ``"tpu"``, ``"cpu"``, ``"auto"``.
      ``"auto"`` uses an accelerator when present, else the host platform.
    - ``coordinator_address`` / ``coordinator_port``: multi-host bootstrap
      rendezvous — the ``spark.oap.mllib.oneccl.kvs.ip``/``.port`` analog
      (reference KMeansDALImpl.scala:39-46). Empty means single-process.
    - ``num_processes`` / ``process_id``: collective world shape — the
      executor-count / rank pair (reference OneCCL.scala:32-42).
    - ``data_axis`` / ``model_axis``: mesh axis names for row sharding and
      feature/factor sharding.
    - ``model_parallel``: size of the model axis in meshes built by
      :func:`~oap_mllib_tpu.parallel.mesh.get_mesh` (devices are arranged
      (n // model_parallel, model_parallel)).  >1 enables mesh-sharded
      linalg — PCA shards its Gram/covariance rows over the model axis so
      the (d, d) accumulation outgrows one chip's HBM (survey §5; the
      reference has no analog because oneDAL kernels are single-node).
    - ``enable_x64``: run K-Means/PCA accumulation in float64 for parity with
      the reference's double kernels (KMeansDALImpl.cpp:32); ALS uses float32
      like the reference (ALSDALImpl.cpp:35).
    - ``fallback``: when True (default), estimators silently fall back to the
      CPU/NumPy reference path if the capability predicate fails — the
      "unmodified user code" contract (reference Utils.scala:98-115).
    - ``timing``: per-phase wall-time logging, the std::chrono log analog
      (reference KMeansDALImpl.cpp:202-222).
    """

    device: str = "auto"
    coordinator_address: str = ""
    coordinator_port: int = 0
    num_processes: int = 1
    process_id: int = 0
    data_axis: str = "data"
    model_axis: str = "model"
    model_parallel: int = 1
    enable_x64: bool = False
    fallback: bool = True
    timing: bool = False
    seed: int = 0
    # MXU precision tier for the K-Means hot loop AND the PCA covariance
    # Gram.  "highest" = full f32 (multi-pass) — the 1e-4 numerical-parity
    # contract.  "high" = bf16_3x: K-Means runs bf16_3x centroid sums +
    # bf16 assignment (within 1e-5 of highest at a fraction of the MXU
    # passes; see kmeans_ops._assign_prec), PCA
    # holds <=1e-4 on the centered Gram.
    # "default" = bf16 everywhere (K-Means ~1e-2, PCA ~1e-3); opt-in for
    # throughput-first workloads.  The x64 lane pins PCA to highest.
    # Per-tier bounds pinned on tests_tpu/; docs/configuration.md has the
    # full table.
    matmul_precision: str = "highest"
    # K-Means hot-loop kernel: "auto" follows kmeans_ops.pallas_preferred
    # — the fused Pallas kernel (one pass over X per iteration, the
    # distance and one-hot blocks never leaving VMEM) at every tier while
    # (k, d) fits the kernel's VMEM blocks, the chunked XLA Lloyd past
    # them.  "xla"/"pallas"
    # force a path; "pallas" requires TPU + single-device + f32 and falls
    # back otherwise.
    kmeans_kernel: str = "auto"
    # ALS normal-equation layout: "auto" uses the scatter-free grouped-edge
    # programs (batched MXU matmuls where COO pays a segment-sum scatter)
    # unless the degree distribution's padding blowup exceeds the guard, in
    # which case the COO segment-sum programs run; "grouped"/"coo" force a
    # layout.  Applies to both the single-device and the block-parallel
    # paths.
    als_kernel: str = "auto"
    # PCA covariance kernel: "auto" runs the fused Pallas moments kernel
    # (ops/pallas/pca_kernel: center + mask + Gram + colsum per row tile
    # in VMEM, no HBM centered temp) when its preconditions hold — TPU,
    # single device, f32, and the (d, d) Gram block fits the kernel's
    # VMEM budget (d <= ~2048) — at every precision tier (the kernel
    # ships the same hand-rolled bf16 hi/lo-split tiers as the K-Means
    # kernel, so the bf16 policy prices ON Pallas).  "xla"/"pallas"
    # force a path; "pallas" still requires the preconditions and falls
    # back otherwise.  Applies to the in-memory AND streamed covariance
    # passes (the model-sharded Gram stays on the shard_map XLA path).
    pca_kernel: str = "auto"
    # ALS normal-equation solve kernel: "auto" runs the batched Pallas
    # assembly+solve kernel (ops/pallas/als_kernel: per-user Gram
    # assembly — moments + ALS-WR regularization + implicit Gram term —
    # and the unrolled rank-r Cholesky in one fused program, batch on
    # the 128-lane axis) when on TPU with f32 factors and rank <= 32;
    # "xla" keeps the batch-wide unrolled XLA solve
    # (ops/als_ops._chol_solve_unrolled); "pallas" forces the kernel
    # (same preconditions, falls back otherwise).  Applies wherever
    # moments meet regularized_solve: single-device grouped/COO and the
    # block-parallel runners.
    als_solve_kernel: str = "auto"
    # Cross-device reduction of per-pass moments (K-Means centroid
    # sums/counts/cost over the data axis, and the streamed multi-host
    # per-pass reductions): "auto"/"on" replace the post-pass psums with
    # the ring reduction (ops/pallas/ring_reduce: reduce-scatter +
    # all-gather rotating fixed segments around the mesh ring —
    # pltpu.make_async_remote_copy DMA on TPU, the identical-schedule
    # ppermute program elsewhere), falling back to the psum path when
    # the mesh has fewer than 2 devices on the reduce axis; "off" keeps
    # the psum path everywhere.  "on" and "auto" are synonyms today
    # (the auto rule may grow shape bounds as TPU measurements land).
    ring_reduction: str = "auto"
    # ALS item-factor layout on the block-parallel path.  "replicated"
    # keeps Y on every device and psums full (n_items, r, r+1) item
    # partials each iteration — one collective, best at small n_items.
    # "sharded" completes the 2-D user x item grid (the reference's
    # per-rank transposed item blocks, ALSDALImpl.cpp:192-214,301-316):
    # Y block-sharded over the data axis, per-iteration collectives are
    # two factor all_gathers — ~(r+1)x less traffic — and the per-rank
    # item partials and resident Y shrink world-fold.  "auto" shards once
    # the replicated psum bytes/iteration exceed
    # ops.als_block.ITEM_SHARD_AUTO_BYTES AND the sharded all_gather
    # traffic is actually lower (user-dominated id spaces stay
    # replicated — ops.als_block.item_layout_sharded).
    als_item_layout: str = "auto"
    # PCA eigensolver.  "eigh" (and "auto", today's resolution of it) =
    # the full d x d factorization — the parity contract, exact for any
    # spectrum.  "randomized" = top-k subspace iteration
    # (ops/pca_ops.topk_eigh_randomized): replaces the O(d^3) eigh that
    # owns most of the large-d wall with a few
    # (d, d) x (d, k+16) MXU matmuls — opt-in because accuracy is
    # spectral-gap-dependent (decaying spectra ~1e-4 vs eigh; a flat
    # spectrum biases values ~5% low and its eigenvectors are
    # ill-defined).  The fit summary records which solver ran.
    pca_solver: str = "auto"
    # Randomized-solver tuning: probe width = k + pca_rand_oversample,
    # subspace iterations = pca_rand_iters.  The defaults hold ~1e-4 on
    # decaying spectra; weakly-gapped spectra tighten with more of both
    # (a d=2048 Wishart edge: ~5% value bias at 8/16, ~0.3% at 16/64).
    # Ignored unless pca_solver="randomized".
    pca_rand_oversample: int = 16
    pca_rand_iters: int = 8
    # Shape bucketing (data/bucketing.py): round padded row counts up to
    # geometric buckets so one compiled program serves a RANGE of input
    # sizes — a service fitting many differently-sized datasets stops
    # paying seconds of XLA compile per request shape.  "on" (default) =
    # x2 steps anchored at the shard multiple; "off" = exact padding
    # (today's shapes); a numeric string (e.g. "1.25") = gentler growth.
    # Padding rows carry mask/weight 0, so per-fit results match the
    # unbucketed path (k-means|| init draws are the one shape-dependent
    # RNG — docs/user-guide.md "Compile amortization" has the caveat and
    # the memory/FLOP cost table).
    shape_bucketing: str = "on"
    # Persistent XLA compilation cache directory (jax
    # compilation_cache_dir, wired by utils/progcache
    # .ensure_persistent_cache at dispatch time).  Non-empty = compiled
    # executables serialize to this dir and a warm process skips XLA
    # compilation entirely — the cross-process half of compile
    # amortization.  Empty (default) = no persistence.
    compilation_cache_dir: str = ""
    # Kernel-geometry autotuner (ops/pallas/autotune.py): tile rows,
    # VMEM rotation depth, solve batch, ring segment counts per
    # (backend, shape-bucket, dtype-tier).  "auto" = launch cached or
    # pinned tuned geometry when available, otherwise the hand-picked
    # defaults — never sweeps, zero overhead.  "on" = sweep the
    # candidate grid on a cache miss (deterministic measured best-of-N;
    # winners persist) so the SECOND fit on the same backend/bucket
    # launches pre-tuned with zero sweep overhead.  "off" = defaults
    # always, cache ignored.  "pin:<json>" = per-kernel geometry pinned
    # verbatim (e.g. 'pin:{"kmeans": {"tile_rows": 1024}}'); unknown
    # kernels/fields raise, like every typo here.
    tuning: str = "auto"
    # Persistent tuning-cache directory: swept winners serialize here
    # (one JSON file per (backend, kernel, shape-bucket, dtype-tier)
    # key) so a FRESH process — or a second fit anywhere on the same
    # backend — launches pre-tuned without re-sweeping.  Empty
    # (default) = in-process memory only.
    tuning_cache_dir: str = ""
    # Streamed-path prefetch depth: how many chunks the background staging
    # thread may hold ahead of the consumer (data/prefetch.py).  2 =
    # double buffering — chunk N+1 is padded/converted/device_put while
    # chunk N's step executes, hiding host->device transfer behind
    # compute.  1 = today's strictly serial stage->transfer->compute loop
    # (no thread; bit-identical results — depth never changes the math,
    # only the overlap).  Each unit of depth holds one extra staged chunk
    # in device memory, so HBM grows by chunk_bytes * (depth - 1).
    prefetch_depth: int = 2
    # -- memory-budget planner (utils/membudget.py) --------------------------
    # HBM budget consulted by the route planner on every accelerated fit:
    # the per-device accelerator memory the fit's working set may occupy.
    # "" (default) = auto-detect (jax device memory_stats bytes_limit;
    # a conservative host-RAM-derived bound on backends that report
    # none); a size string ("4G", "512M", "1073741824") pins it; "0" or
    # "unlimited" disables the HBM constraint.  Budgets only steer ROUTE
    # selection (in-memory / chunked / streamed / streamed-block) — they
    # never reject a fit outright unless scale_policy="strict".
    memory_budget_hbm: str = ""
    # Host-RAM budget for staged tables (same grammar): the planner
    # routes fits whose staged host footprint exceeds it onto
    # disk-backed streaming, and the resilience ladder's spill rung
    # re-enters the streamed route from disk after a host-classified
    # OOM.  "" = auto-detect from the machine's physical memory.
    memory_budget_host: str = ""
    # What the planner does when the budget forces a route below the
    # fit's natural one: "auto" (default) picks the cheapest route that
    # fits the budgets, degrading loudly (warning log + the full
    # decision in summary.route) but never silently; "strict" raises
    # BudgetError instead of degrading scale (operators who must never
    # absorb a slow route without knowing); "pin:<route>" forces one of
    # in-memory|chunked|streamed|streamed-block, budgets advisory.  A
    # typo raises at fit entry (the kmeans_kernel contract).
    scale_policy: str = "auto"
    # Directory for spilled tables (the resilience ladder's host-OOM
    # rung stages the source to disk here and re-enters the streamed
    # route; utils/membudget.spill_source).  "" = the platform temp dir.
    spill_dir: str = ""
    # -- resilience layer (utils/resilience.py, utils/faults.py) ------------
    # Fault-injection spec: comma-separated "site:kind=count" entries
    # arming deterministic faults at named runtime sites (stream.read,
    # prefetch.stage, bootstrap.connect, fit.execute) — e.g.
    # "stream.read:fail=2" makes the first two chunk reads raise a
    # transient error.  Empty = no injection.  Grammar and sites:
    # utils/faults.py; CI drives every retry tier through this
    # (dev/fault_gate.py).
    fault_spec: str = ""
    # What a streamed-path numerical guardrail does when it detects
    # NaN/Inf in a training iterate (K-Means centroids, ALS factors, the
    # PCA Gram accumulator, checked after each pass): "raise" surfaces a
    # NonFiniteError immediately; "fallback" degrades to the CPU/NumPy
    # reference path (subject to Config.fallback).
    nonfinite_policy: str = "raise"
    # Max transient-fault retries per fit attempt ladder (exponential
    # backoff with deterministic jitter; utils/resilience.RetryPolicy).
    retry_limit: int = 5
    # Backoff base in seconds: retry n sleeps ~ retry_backoff * 2^n,
    # capped at 2 s, jittered deterministically.
    retry_backoff: float = 0.05
    # Retry wall-clock budget in seconds: retries stop when the next
    # backoff would cross this deadline, even with retries left.
    retry_deadline: float = 30.0
    # Coordinator-connection budget for initialize_distributed, in
    # seconds: connection attempts retry with backoff until this
    # deadline, then fail with an error naming coordinator/rank/elapsed.
    bootstrap_timeout: float = 60.0
    # -- live-world recovery plane (utils/recovery.py, utils/supervisor.py) --
    # Collective deadline in seconds: > 0 arms a watchdog on every
    # host-level collective dispatch (the eager facade in
    # parallel/collective.py, the host-mediated reductions in
    # ops/stream_ops.py, the checkpoint agreement gathers, the sanitizer
    # cross-check) in multi-process worlds.  A peer that never shows up
    # raises CollectiveTimeoutError on every surviving rank — naming
    # op/axis/elapsed and the last-completed dispatch fingerprint —
    # instead of hanging until the distributed timeout.  0 (default) =
    # disarmed: the hot path is one config check per dispatch.  Negative
    # values raise.
    collective_timeout: float = 0.0
    # Recovery sideband directory: non-empty arms coordinated abort — a
    # rank's fatal fault writes a machine-readable crash record
    # (crash.rank<r>.json: rank, site, fault class, last durable
    # checkpoint step, telemetry snapshot) that poisons its peers: ranks
    # waiting inside a deadline-armed collective see the record and
    # raise PeerAbortError promptly instead of timing out.  The
    # supervisor (utils/supervisor.py) sets this for every rank it
    # launches and classifies the records at exit.  Multi-process worlds
    # need a filesystem shared by every rank.  Empty (default) = off.
    crash_dir: str = ""
    # Supervisor restart budget: how many relaunches
    # utils/supervisor.Supervisor may spend before giving up on a world.
    restart_budget: int = 3
    # Supervisor relaunch backoff base in seconds: relaunch n sleeps
    # restart_backoff * 2^(n-1) before spawning the new world.
    restart_backoff: float = 1.0
    # How many CONSECUTIVE failures attributed to the same rank before
    # the supervisor shrinks the world by one (excluding the repeatedly
    # bad slot) and lets resume=auto reshard state onto the new layout.
    shrink_after: int = 2
    # Seeded randomized chaos schedule over every registered fault site
    # (utils/faults.py): "seed:rate[:kinds[:budget]]" — e.g. "7:0.02"
    # fires a transient fault on ~2% of site calls, "7:0.01:kill:1"
    # hard-kills the process (SIGKILL — a preemption) at most once.
    # kinds is a "+"-separated subset of fail|oom|nan|err|kill (cycled
    # deterministically); budget caps total fired faults ("*" =
    # unbounded).  The schedule is a pure function of
    # (seed, process index, site, call index), so drills are
    # reproducible and ranks fail independently.  Empty = off.
    chaos: str = ""
    # -- elastic worlds: sharded checkpoint/resume (utils/checkpoint.py) -----
    # Checkpoint directory for iterate-state checkpoints.  Non-empty arms
    # periodic per-rank sharded checkpoints on every fit path (K-Means
    # centroids, ALS factor shards, PCA streamed moments, plus the
    # pass/iteration index and world layout), written atomically
    # (tmp+rename, manifest last) so a preempted worker can be relaunched
    # and resume mid-fit — in a DIFFERENT world size if needed (factor
    # shards are redistributed through a collective resharding pass at
    # restore).  Multi-process worlds require this to be a filesystem
    # shared by every rank.  Empty (default) = checkpointing off, zero
    # overhead (one string check per fit).
    checkpoint_dir: str = ""
    # How often to checkpoint, in iterate-loop steps (streamed passes /
    # ALS iterations; in-memory fits run their compiled loops in
    # interval-sized segments and checkpoint between segments).  1
    # (default) = every step.
    checkpoint_interval: int = 1
    # Restore policy when checkpoint_dir is armed: "auto" (default)
    # resumes from a matching checkpoint when one exists and silently
    # starts fresh otherwise (a corrupt or mismatched checkpoint also
    # falls back to fresh, with a warning); "require" raises
    # CheckpointError unless a valid checkpoint was restored (operators
    # who must never silently recompute); "off" never restores but still
    # writes (produce checkpoints without consuming them).
    resume: str = "auto"
    # -- mixed-precision compute policy (utils/precision.py) -----------------
    # Process-wide input/accumulation precision for the matmul-dominated
    # hot paths (K-Means Lloyd distances + centroid sums, PCA
    # Gram/colsum, ALS normal-equation moments), in-memory AND streamed:
    # "f32" = today's behavior, bit-compatible (operands stay f32, dots
    # run at matmul_precision); "tf32" = f32 operands, bf16_3x dots
    # (lax.Precision.HIGH — the TPU analog of TF32, ~1e-5 of full f32);
    # "bf16" = operands cast to bfloat16 (at STAGING time on streamed
    # paths, halving host->device bytes) with f32 accumulators — solves,
    # norms, and convergence state stay f32; "auto" = bf16 where a
    # parity bound is registered for the algorithm and the backend has
    # fast bf16 MXUs, else f32.  enable_x64 pins every fit to f32.  A
    # non-finite iterate under a reduced policy degrades the fit to f32
    # via the resilience ladder's precision rung instead of failing.
    # Parity bounds + gate: utils/precision.py, dev/precision_gate.py.
    compute_precision: str = "f32"
    # Per-algorithm overrides of compute_precision (same vocabulary,
    # including "auto"); empty = inherit.  E.g. kmeans_precision="bf16"
    # runs only K-Means reduced while PCA/ALS stay at the global policy.
    kmeans_precision: str = ""
    pca_precision: str = ""
    als_precision: str = ""
    # -- serving plane (oap_mllib_tpu/serving/) ------------------------------
    # Compute policy for serving-time scoring matmuls (the registry /
    # micro-batcher request paths and the full-sweep top-k).  "" (the
    # default) inherits the algorithm's resolved compute policy
    # (compute_precision + per-algo overrides) — f32 stays
    # bit-compatible with direct model calls; "f32"|"tf32"|"bf16"|
    # "auto" override it for serving only (a bf16 serving tier halves
    # the request staging bytes while fits keep f32).  A typo raises at
    # request time.
    serving_precision: str = ""
    # Row-chunk width of the full-sweep top-k (serving/sweep.py): how
    # many query (user) rows score per compiled step while the sweep
    # streams the factor table through the prefetch pipeline.  0 (the
    # default) derives the width from the shared scoring live-buffer
    # budget (ops/kmeans_ops.rows_per_chunk — the same bound the
    # models' chunked top-k uses), so the (chunk, n_items) score block
    # stays bounded whatever the table sizes.  Negative raises.
    sweep_chunk_rows: int = 0
    # -- traffic plane (serving/traffic.py): async ingestion, admission,
    #    replica scaling ------------------------------------------------------
    # Max pending async requests a TrafficQueue holds before submit
    # sheds with ShedError(reason="queue_full") + oap_serve_shed_total.
    # The bound is requests (not rows): it caps dispatcher latency per
    # pump cycle.  Must be >= 1; a typo raises at submit time.
    serve_queue_depth: int = 256
    # Default per-request deadline in milliseconds for submits that
    # don't pass deadline_ms explicitly.  Requests still pending past
    # their deadline are shed before dispatch (their future raises
    # ShedError(reason="deadline")) — never scored dead.  0 (default) =
    # no deadline; negative raises.
    serve_deadline_ms: float = 0.0
    # Fraction of the resolved HBM budget (utils/membudget.Budgets —
    # memory_budget_hbm or auto-detect) the traffic queue's staged
    # working set may claim before submit sheds with
    # ShedError(reason="budget"): pending + incoming request bytes x
    # the planner's overhead fudge > hbm_budget x this headroom =>
    # shed instead of OOM.  Only armed when the budget resolves > 0
    # (an unbounded budget prices nothing).  Must be in (0, 1]; a typo
    # raises at submit time.
    serve_shed_headroom: float = 0.5
    # Scale-out trigger for the serving replica controller
    # (serving/traffic.ScaleController): windowed mean queue depth PER
    # REPLICA above this (with a non-falling depth trend) votes one
    # replica out, booked in oap_serve_scale_out_total and the
    # supervisor sideband hint.  Must be > 0.
    serve_scale_high: float = 32.0
    # Scale-in trigger: a fleet idle (zero queue depth, no new
    # requests) for this many seconds sheds one replica down to the
    # controller's floor.  Must be > 0.
    serve_scale_idle_s: float = 30.0
    # Durable-future retry envelope (serving/traffic.py): how many times
    # an ADMITTED request may be re-enqueued after a transient scoring
    # fault before its future fails with a classified ServeError
    # (reason="retries-exhausted").  Re-enqueued requests keep their
    # original deadline and arrival order, so retries never jump the
    # deadline priority.  0 = fail on the first transient fault; must
    # be >= 0 (a typo raises at submit time).
    serve_retry_limit: int = 2
    # Backoff base in seconds for re-enqueued requests: retry n waits
    # ~ serve_retry_backoff * 2^n before redispatch, jittered
    # deterministically per (site, attempt) like
    # utils/resilience.RetryPolicy.  Must be >= 0; a typo raises at
    # submit time.
    serve_retry_backoff: float = 0.01
    # Brownout degradation ladder (serving/traffic.BrownoutController):
    # what the traffic plane does under SUSTAINED over-budget pressure
    # (membudget-priced admission, fleet-trend-gated like the scale
    # controller) before it sheds.  "auto" (default) steps through the
    # recorded rungs — "topk" (halved top-k depth), "bf16" (serving
    # precision drops to bf16 where a parity bound is registered),
    # "stale" (stale-pin answering during model re-pin) — absorbing
    # over-budget requests while rungs remain; each step is LOUD
    # (serving_summary()["brownout"], span attrs,
    # oap_serve_brownout_rung, the flight recorder).  "off" disarms the
    # ladder (over-budget requests shed immediately, today's
    # behavior); "pin:<rung>" holds a fixed rung (off|topk|bf16|stale)
    # without automatic stepping.  A typo raises at submit time.
    serve_brownout: str = "auto"
    # Request-lifecycle tracing (serving/reqtrace.py): > 0 arms a trace
    # context on every ADMITTED request — a deterministic id plus a
    # fixed-schema deadline-budget ledger (admission / queue_wait /
    # batch_form / bucket_pad / compile / execute / dispatch stage
    # walls that sum to the measured request wall by construction),
    # attached to the answered future (serving.ledger_of), booked into
    # the oap_serve_stage_seconds{stage=} histograms, and folded into
    # serving_summary()["attribution"].  The value is the SAMPLING
    # fraction for heavy emission (flight-recorder request events,
    # JSONL "request" records, /metrics exemplars): a request is
    # sampled when crc32(trace_id)/2^32 < serve_trace_sample — a pure
    # hash, no RNG, so every process of a world samples the same ids.
    # 0 (default) = off, one config check per submit; must be in
    # [0, 1], a typo raises at submit time.
    serve_trace_sample: float = 0.0
    # Serving latency SLO target in milliseconds (serving/slo.py): > 0
    # arms the multi-window burn-rate error-budget engine — a request
    # is "bad" when it fails/sheds or its wall exceeds this p99 target;
    # burn rates over the fast (serve_slo_window_s / 12) and slow
    # (serve_slo_window_s) windows land in oap_slo_burn_rate{window=},
    # oap_slo_error_budget_remaining, serving_summary()["slo"], the
    # /sloz endpoint, and every scale/brownout decision (observe-only:
    # the SLO state is RECORDED with the decision, it never makes one).
    # 0 (default) = disarmed; must be >= 0.
    serve_slo_p99_ms: float = 0.0
    # Availability objective for the error-budget engine: the target
    # fraction of requests answered within SLO (e.g. 0.999 = a 0.1%
    # error budget).  Burn rate 1.0 means bad requests arrive exactly
    # at the rate that exhausts the budget over the window.  Must be in
    # (0, 1); a typo raises when the engine is consulted.
    serve_slo_availability: float = 0.999
    # Slow burn-rate window in seconds (the error-budget accounting
    # horizon); the fast window is this / 12 (the SRE 5m/1h pairing).
    # Must be > 0.
    serve_slo_window_s: float = 3600.0
    # -- online / incremental fits (oap_mllib_tpu/online/) -------------------
    # Count-decay factor for mini-batch Lloyd (online/minibatch.py
    # KMeansModel.partial_fit): each partial_fit multiplies the
    # accumulated per-center counts by this BEFORE folding the new
    # mini-batch in, so the per-center learning rate
    # counts_new / (decay * counts_old + counts_new) forgets old data
    # geometrically.  1.0 (default) = no forgetting — the streaming
    # average converges to the full-batch Lloyd step over the union of
    # all chunks seen; values in (0, 1) track drifting distributions.
    # Must be in (0, 1]; a typo raises at partial_fit entry.
    online_decay: float = 1.0
    # Row batching for the ALS fold-in solve (online/foldin.py): how
    # many touched user/item rows solve per normal-equation launch.  0
    # (default) solves every touched row in ONE batched launch — the
    # fold-in contract (the per-delta cost is one edge pass + one
    # solve, never a full refit); > 0 chunks huge deltas so the
    # (batch, r, r) moment block stays bounded.  Negative raises.
    online_foldin_batch: int = 0
    # In-place serving re-pin on delta commit (online/delta.py): "auto"
    # (default) re-pins every registry handle serving the committed
    # model — version bump + fresh device pins under the registry lock,
    # in-flight requests keep answering, zero new XLA compiles while
    # shapes stay in-bucket — and resets the
    # oap_serve_model_staleness_seconds gauge; "off" leaves served
    # handles on the old pin (they go stale, LOUD via the staleness
    # gauge) until the caller re-serves explicitly.  A typo raises at
    # commit time.
    online_repin: str = "auto"
    # -- telemetry layer (oap_mllib_tpu/telemetry/) --------------------------
    # jax.profiler trace directory: non-empty wraps every estimator fit
    # in a profiler trace written there (utils/profiling.maybe_trace),
    # and the span tree emits a TraceAnnotation per phase while the
    # trace is live.  Promoted from the raw OAP_MLLIB_TPU_PROFILE_DIR
    # env read so Config.set/scoped overrides work like every other
    # knob; the env var still applies through the standard coercion.
    profile_dir: str = ""
    # Runtime sanitizer plane (utils/sanitizers.py): comma-set of
    # "collective", "transfer", "retrace", "locks"; empty (default) =
    # all off.  "collective" fingerprints every host-level collective
    # dispatch as (op, axis, shape, dtype) and cross-checks the
    # signature across ranks BEFORE dispatch (plus a per-fit
    # fingerprint check at finalization), so a rank-divergent
    # collective raises a diagnostic naming the mismatching op on every
    # rank instead of hanging the world.  "transfer" runs streamed
    # per-chunk consumer bodies under jax.transfer_guard("disallow") —
    # implicit device<->host syncs in the hot loop fail loudly (the
    # runtime ground truth behind oaplint R4).  "retrace" asserts zero
    # new XLA compiles after warmup in steady-state chunk loops (and
    # via sanitizers.steady_state scopes).  "locks" arms the tracked-
    # lock seams (utils/locktrace.py): a live lock-order inversion
    # raises LockOrderError naming both witness stacks instead of
    # deadlocking, hold times feed the oap_lock_hold_seconds histogram,
    # and holds exceeding collective_timeout are flagged (never killed)
    # — the runtime half of the oaplint concurrency pass (R19-R22).
    # Off = one cached string check per seam (~0% overhead,
    # dev/sanitizer_gate.py and dev/concurrency_gate.py assert it); on
    # adds one tiny allgather per host collective under "collective".
    # docs/distributed.md "Sanitizers" has the when/why table.
    sanitizers: str = ""
    # JSON-lines telemetry sink: non-empty appends one record per span
    # close plus a registry snapshot at every fit finalization (and a
    # final snapshot at process exit).  Multi-process worlds write
    # per-rank files (<path>.rank<r>), each record rank-tagged, so a
    # world's files concatenate into one mergeable stream
    # (telemetry/export.py; docs/observability.md).  Empty = off (the
    # near-zero-overhead default: no file is ever opened).
    telemetry_log: str = ""
    # -- fleet observability control plane (telemetry/fleet.py,
    #    telemetry/flightrec.py) --------------------------------------------
    # Live metrics exposition port: > 0 starts one stdlib http.server
    # daemon thread per rank serving GET /metrics (the Prometheus text
    # exposition of the process registry) and GET /healthz (fit root,
    # step, last-collective fingerprint, resilience ladder state) on
    # port metrics_port + process_id — every rank of a co-hosted
    # pseudo-cluster world gets its own scrape surface.  0 (default) =
    # no server, zero overhead; negative raises.
    metrics_port: int = 0
    # Cross-rank fleet rollups: "auto" (default) arms per-pass rollups
    # only in multi-process worlds (single-process fits pay one config
    # check); "on" arms them everywhere (a 1-rank world folds its own
    # frame — useful for tests and single-host dashboards); "off"
    # disarms them.  Armed, every streamed pass allgathers one
    # fixed-shape per-rank stat frame (pass wall, stage/transfer/compute
    # split, bytes staged, retries, kernel dispatch wall) over the host
    # collective plane (deadline-watchdog guarded), folds it into
    # oap_fleet_* gauges/histograms on rank 0, and lands a `fleet` block
    # (slowest rank, skew ratio, imbalance trend) in the fit summary.
    # A typo raises.
    fleet_stats: str = "auto"
    # -- heterogeneous fleets: capability-weighted sharding
    #    (parallel/balance.py, utils/dispatch.throughput_probe) ---------------
    # Capability-weighted shard planning: "auto" (default) arms the
    # balance plane in multi-process worlds — per-rank capability
    # weights (probed or pinned) convert into uneven per-rank row
    # extents for streamed fits built through balance.local_source and
    # uneven user-block offsets for replicated-layout block ALS, so a
    # mixed or degraded world finishes passes together instead of at
    # the slowest rank's pace; "on" arms it everywhere (a 1-rank world
    # degenerates to the equal plan — tests, dashboards); "off" keeps
    # equal shards (the planner still runs where consulted, with
    # origin="equal").  A typo raises.
    capability_sharding: str = "auto"
    # Per-rank capability override.  "" (default) = measure: a tiny
    # deterministic-seeded matmul + host->device stream microbench
    # (utils/dispatch.throughput_probe), cached per process.  A bare
    # float ("0.25") pins THIS rank's capability; a comma map keyed by
    # rank ("0:1.0,1:0.25") pins per rank (tests / known-heterogeneous
    # deployments — ranks absent from the map fall back to the probe).
    # Values must be > 0; a typo raises.
    rank_capability: str = ""
    # Capability-probe generation.  The probe cache
    # (utils/dispatch.throughput_probe, parallel/balance
    # .world_capabilities) is keyed by this epoch: bumping it
    # invalidates every cached measurement so the next consult
    # re-probes.  The supervisor (utils/supervisor.py) sets
    # OAP_MLLIB_TPU_PROBE_EPOCH to the attempt number on every
    # (re)launch, so a relaunched rank measures its CURRENT capability
    # instead of trusting its pre-preemption value.  Default 0.
    probe_epoch: int = 0
    # Live straggler rebalancing trigger (parallel/balance.py, riding
    # the fleet rollups): when a pass's skew ratio (max/mean per-rank
    # pass wall) exceeds this for rebalance_patience consecutive passes
    # and the imbalance trend is not falling, the controller re-plans
    # extents at the next pass boundary from the measured per-rank
    # throughput.  Must be > 1.0; rebalancing also requires
    # Config.fleet_stats armed (the rollups are its measurement layer).
    rebalance_threshold: float = 1.5
    # How many CONSECUTIVE over-threshold passes before a re-plan (>= 1).
    rebalance_patience: int = 3
    # Flight recorder ring size, in event slots: > 0 arms a
    # constant-memory per-rank ring buffer (telemetry/flightrec.py) of
    # recent events — span open/close, host-collective dispatch
    # fingerprints, fault/retry/degradation events, checkpoint commits —
    # each stamped with a monotonic seq.  Crash records
    # (utils/recovery.py) embed the tail, so every post-mortem shows the
    # last N events on every rank; the JSONL telemetry sink drains new
    # events at each fit finalization (dev/oaptrace.py merges them into
    # a Perfetto-loadable timeline).  0 (default) = off, one config
    # check per would-be event; negative raises.
    flight_recorder: int = 0

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls()
        for f in dataclasses.fields(cls):
            env_key = _ENV_PREFIX + f.name.upper()
            if env_key not in os.environ:
                continue
            raw = os.environ[env_key]
            if f.type in ("bool", bool):
                setattr(cfg, f.name, _env_bool(raw))
            elif f.type in ("int", int):
                setattr(cfg, f.name, int(raw))
            elif f.type in ("float", float):
                setattr(cfg, f.name, float(raw))
            else:
                setattr(cfg, f.name, raw)
        return cfg


_lock = threading.Lock()
_config: Optional[Config] = None


def get_config() -> Config:
    """Return the process-global config, initializing from env on first use."""
    global _config
    with _lock:
        if _config is None:
            _config = Config.from_env()
        return _config


def set_config(**updates) -> Config:
    """Update the process-global config in place; returns it."""
    cfg = get_config()
    with _lock:
        for k, v in updates.items():
            if not hasattr(cfg, k):
                raise ValueError(f"unknown config field: {k!r}")
            setattr(cfg, k, v)
    return cfg
