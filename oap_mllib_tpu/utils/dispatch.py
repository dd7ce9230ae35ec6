"""Capability check + transparent dispatch.

Replaces the reference's L5 runtime dispatch: the cluster-wide platform
compatibility gate (``Utils.checkClusterPlatformCompatibility`` running
``daal_check_is_intel_cpu()`` on driver + every executor, reference
Utils.scala:98-115 / OneDAL.cpp:96-102) and the per-algorithm guards in the
Spark shims (e.g. euclidean-only + no-weight for K-Means,
spark-3.1.1/ml/clustering/KMeans.scala:349-351; d<65535 for PCA,
PCA.scala:103; implicitPrefs for ALS, ALS.scala:925).

Semantics preserved: when the predicate fails and ``config.fallback`` is
True, the estimator silently runs the CPU/NumPy reference path — user code
unchanged.  When fallback is disabled, failing the predicate raises.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax

from oap_mllib_tpu.config import get_config

log = logging.getLogger("oap_mllib_tpu")

# PCA feature-count guard, mirroring the reference's numFeatures < 65535
# (spark-3.1.1/ml/feature/PCA.scala:103) — there it is a oneDAL table limit,
# here it bounds the replicated d x d Gram matrix (65534^2 f64 ~ 34 GB is
# far past one chip's HBM; realistic ceiling enforced at estimator level).
MAX_PCA_FEATURES = 65535


def accelerator_available() -> bool:
    """True if a non-CPU XLA backend is present (~ daal_check_is_intel_cpu)."""
    try:
        return jax.devices()[0].platform != "cpu"
    except RuntimeError:
        return False


def platform_compatible() -> bool:
    """Cluster-wide compatibility: can we run compiled sharded programs?

    Single-process: any JAX backend works (CPU included — the CPU backend is
    this framework's 1-rank pseudo-cluster, like the reference's local[*]
    1-rank CCL world, Utils.scala:119-121).  The ``device`` config forces the
    decision either way.
    """
    cfg = get_config()
    if cfg.device == "cpu":
        return False
    if cfg.device == "tpu":
        return accelerator_available()
    # auto: accelerated path whenever JAX initializes at all
    try:
        jax.devices()
        return True
    except RuntimeError:
        return False


def should_accelerate(algo: str, guard_ok: bool, reason: str = "") -> bool:
    """Decide accelerated vs. fallback path; raise if fallback disabled.

    Every estimator fit funnels through here, so this is also where the
    persistent XLA compilation cache is wired (Config
    .compilation_cache_dir -> jax compilation_cache_dir, idempotent) —
    before the first program of the fit traces — and where the kernel
    autotuner's mode string is validated (ops/pallas/autotune.parse_mode:
    a Config.tuning typo raises HERE, at fit entry, not deep inside a
    kernel launch)."""
    cfg = get_config()
    from oap_mllib_tpu.utils.progcache import ensure_persistent_cache

    ensure_persistent_cache(cfg.compilation_cache_dir)
    from oap_mllib_tpu.ops.pallas.autotune import parse_mode

    parse_mode(cfg.tuning)
    ok = platform_compatible() and guard_ok
    if ok:
        return True
    if not guard_ok:
        why = reason or "guard failed"
    else:
        why = "platform incompatible"
    if not cfg.fallback:
        raise RuntimeError(
            f"{algo}: accelerated path unavailable ({why}) and fallback disabled"
        )
    log.info("%s: falling back to CPU reference path (%s)", algo, why)
    return False


# ---------------------------------------------------------------------------
# Per-rank throughput probe (ISSUE 15: capability-weighted sharding)
# ---------------------------------------------------------------------------

# probe geometry: small enough to cost tens of milliseconds anywhere,
# big enough that the matmul leg exercises the MXU/BLAS path and the
# stream leg a real host->device transfer (1 MB)
_PROBE_DIM = 256
_PROBE_STREAM_ROWS = 1024
_PROBE_CHAIN = 8  # chained matmuls per timed launch (amortizes dispatch)
_PROBE_REPS = 3
# reference walls a "typical" host lands near, so capability ~= 1.0 on
# ordinary hardware and the weights read as relative speeds.  Absolute
# calibration does not matter — the planner normalizes to mean 1 — but
# a stable scale keeps logs and pinned-vs-probed values comparable.
_PROBE_REF_COMPUTE_S = 2e-3
_PROBE_REF_STREAM_S = 1e-3

_probe_cache: dict = {}


def throughput_probe(seed: int = 0) -> float:
    """This rank's measured throughput capability (relative scalar, > 0).

    A tiny calibrated microbench: ``_PROBE_CHAIN`` chained
    (256, 256) matmuls through one registry-cached compiled program
    (the compute leg) plus a 1 MB host->device stage (the stream leg),
    best-of-``_PROBE_REPS`` each, combined harmonically — a rank slow at
    EITHER leg is a slow rank (streamed passes pay both).  The input is
    deterministic-seeded so every rank times the same program on the
    same bits; the result is cached per process per
    ``Config.probe_epoch`` (the once-per-fit-start allgather in
    ops/stream_ops.capability_sync reads the cache).  The supervisor
    bumps the epoch on every relaunch attempt, so a relaunched rank
    re-measures its CURRENT capability instead of trusting its
    pre-preemption value.  ``Config.rank_capability`` pins the value
    instead (tests, known deployments) — see :func:`pinned_capability`.
    """
    key = (int(seed), int(get_config().probe_epoch))
    if key in _probe_cache:
        return _probe_cache[key]
    import numpy as np

    from oap_mllib_tpu.utils.progcache import get_or_build

    rng = np.random.default_rng(seed)
    a = np.asarray(rng.normal(size=(_PROBE_DIM, _PROBE_DIM)), np.float32)
    stream_buf = np.asarray(
        rng.normal(size=(_PROBE_STREAM_ROWS, _PROBE_DIM)), np.float32
    )

    def _build():
        import jax
        import jax.numpy as jnp

        def chain(x):
            y = x
            for _ in range(_PROBE_CHAIN):
                y = jnp.dot(y, x, precision=jax.lax.Precision.HIGHEST)
                # renormalize so the chain cannot overflow whatever the
                # seed drew; one cheap VPU op per matmul
                y = y * (1.0 / jnp.maximum(jnp.max(jnp.abs(y)), 1.0))
            return y

        return jax.jit(chain)

    import jax

    fn = get_or_build(
        "dispatch.probe",
        (jax.default_backend(), _PROBE_DIM, _PROBE_CHAIN),
        _build,
    )
    aj = jax.device_put(a)
    np.asarray(fn(aj))  # warm: compile + first dispatch
    compute_s = min(
        _timed(lambda: np.asarray(fn(aj))) for _ in range(_PROBE_REPS)
    )
    np.asarray(jax.device_put(stream_buf))[0, 0]  # warm the transfer path
    stream_s = min(
        _timed(lambda: np.asarray(jax.device_put(stream_buf))[0, 0])
        for _ in range(_PROBE_REPS)
    )
    c = _PROBE_REF_COMPUTE_S / max(compute_s, 1e-9)
    s = _PROBE_REF_STREAM_S / max(stream_s, 1e-9)
    cap = 2.0 / (1.0 / max(c, 1e-9) + 1.0 / max(s, 1e-9))  # harmonic mean
    cap = max(float(cap), 1e-6)
    _probe_cache[key] = cap
    log.info(
        "throughput probe: compute %.3f ms, stream %.3f ms -> "
        "capability %.3f", compute_s * 1e3, stream_s * 1e3, cap,
    )
    return cap


def _timed(fn) -> float:
    from oap_mllib_tpu.utils.timing import tick

    elapsed = tick()
    fn()
    return elapsed()


def pinned_capability(cfg=None) -> Optional[float]:
    """The pinned capability for THIS rank from ``Config.rank_capability``,
    or None when the probe should run.  Grammar: ``""`` = probe; a bare
    float (``"0.25"``) pins this rank; a comma map keyed by rank
    (``"0:1.0,1:0.25"``) pins per rank — ranks absent from the map fall
    back to the probe.  Values must be > 0; a typo raises (the
    kmeans_kernel/fault_spec contract: a capability that silently parses
    to nothing defeats the planner)."""
    from oap_mllib_tpu.config import get_config as _gc

    cfg = cfg or _gc()
    spec = cfg.rank_capability.strip()
    if not spec:
        return None

    def _value(tok: str) -> float:
        try:
            v = float(tok)
        except ValueError:
            raise ValueError(
                "rank_capability must be empty (probe), a float, or a "
                f"comma map 'rank:value,...'; got {cfg.rank_capability!r}"
            ) from None
        if v <= 0:
            raise ValueError(
                f"rank_capability values must be > 0, got {tok!r}"
            )
        return v

    if ":" not in spec:
        return _value(spec)
    rank = _probe_rank()
    found = None
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        if ":" not in entry:
            raise ValueError(
                "rank_capability map entries must be 'rank:value', got "
                f"{entry!r}"
            )
        r_s, v_s = entry.split(":", 1)
        try:
            r = int(r_s)
        except ValueError:
            raise ValueError(
                f"rank_capability map rank must be an int, got {r_s!r}"
            ) from None
        v = _value(v_s)
        if r == rank:
            found = v
    return found


def _probe_rank() -> int:
    try:
        return int(jax.process_index())
    except RuntimeError:
        from oap_mllib_tpu.config import get_config as _gc

        return int(_gc().process_id)


def rank_capability(seed: int = 0) -> "tuple[float, str]":
    """This rank's capability weight and its origin: ``("pinned", v)``
    from ``Config.rank_capability`` when it covers this rank, else the
    cached :func:`throughput_probe` measurement."""
    pinned = pinned_capability()
    if pinned is not None:
        return pinned, "pinned"
    return throughput_probe(seed), "probe"


def _reset_probe_for_tests() -> None:
    _probe_cache.clear()


def allow_fallback(algo: str, why: str) -> bool:
    """The DYNAMIC half of the fallback contract: may a fit that already
    passed :func:`should_accelerate` but then faulted at runtime degrade
    to the CPU reference path?

    ``should_accelerate`` is the static gate (decided once, up front);
    this is its runtime twin, consulted by the resilience ladder
    (utils/resilience.resilient_fit) as its final rung after transient
    retries and the halved-chunk OOM rung are exhausted.  Same knob
    (``Config.fallback``), same logging shape — so the escalation is
    visible in logs exactly like a static fallback, just with the fault
    that caused it."""
    cfg = get_config()
    if not cfg.fallback:
        return False
    log.warning("%s: degrading to CPU reference path (%s)", algo, why)
    return True
