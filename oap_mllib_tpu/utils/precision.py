"""Process-wide mixed-precision compute policy: bf16/TF32 matmul paths
with f32 accumulation.

The matmul-dominated hot paths (the K-Means Lloyd cross-distances, the
PCA Gram/colsum, the ALS normal-equation moments) all ran at full
f32/``matmul_precision`` while the TPU's native bf16 MXU throughput
(~2x FLOPs, half the HBM bytes per operand) sat idle.  The
linear-algebraic formulation of these kernels (cf. arXiv:2601.17136's
communication-avoiding kernel K-Means) is exactly the shape where
reduced-precision INPUTS with f32 ACCUMULATION is a bounded-error win,
so this module makes the trade a first-class, per-fit policy:

======  ====================================================================
tier    meaning
======  ====================================================================
f32     today's behavior, bit-compatible: operands stay f32 and every dot
        runs at the configured ``matmul_precision`` tier (the default)
tf32    f32 operands, dots at ``lax.Precision.HIGH`` (bf16_3x — the TPU
        analog of NVIDIA's TF32: reduced-precision multiplies, f32
        accumulation, ~1e-5 of full f32)
bf16    operands cast to bfloat16 — at STAGING time on the streamed paths,
        so host->device transfer bytes halve too — with every dot
        accumulating in f32 (``preferred_element_type``); solves, norms,
        centroid/Gram/moment accumulators and convergence state stay f32
auto    bf16 where a parity bound is registered for the algorithm AND the
        backend has fast bf16 MXUs (mirroring the ``pallas_preferred``
        auto-rule's measured-shapes contract), f32 otherwise
======  ====================================================================

Resolution (:func:`resolve`) honors per-algorithm overrides
(``Config.kmeans_precision`` / ``pca_precision`` / ``als_precision``;
empty inherits ``Config.compute_precision``), pins f32 under
``enable_x64`` (f64 has no bf16 fast path to buy anything with), and
respects the resilience ladder's f32-degradation scope
(:func:`force_f32`): a non-finite iterate under a reduced-precision
policy steps the ladder's ``precision`` rung — the fit retries at f32
instead of failing (utils/resilience.resilient_fit).

The chosen policy is recorded in every accelerated fit summary
(``precision``), on the fit's span-tree root (``attrs["precision"]``,
exported through the telemetry JSONL sink), and in bench JSON.
``dev/precision_gate.py`` asserts the registered parity bounds and that
the f32 policy reproduces pre-policy numerics bit-for-bit.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

import numpy as np

from oap_mllib_tpu.config import get_config

TIERS = ("f32", "tf32", "bf16")
CHOICES = TIERS + ("auto",)
ALGOS = ("kmeans", "pca", "als")

# Registered bf16-vs-f32 parity bounds per algorithm, on the fixed-seed
# gate datasets (dev/precision_gate.py asserts them; tests/test_precision
# .py pins them on smaller shapes).  `auto` resolves to bf16 ONLY for
# algorithms registered here — an algorithm without a measured bound must
# not be silently downgraded (the pallas_preferred contract: auto picks
# the fast path only where it was measured safe).  Bounds reflect bf16's
# ~8-bit mantissa (~4e-3 relative per rounding) amplified by the
# conditioning of each estimator's reduction:
PARITY_BOUNDS = {
    # converged centroids (relative to the data scale) and relative cost
    # — cost is the tight bound: bf16 rounding can tie-break boundary
    # points differently and settle a NEARBY local optimum of the same
    # quality, so the centroid bound absorbs benign assignment flips
    "kmeans": {"centroid_rel": 5e-2, "cost_rel": 1e-2},
    # top-k principal-subspace angle (radians) + explained-variance-ratio
    "pca": {"subspace_rad": 5e-2, "ratio_abs": 1e-2},
    # factor RMSE relative to the factor scale + prediction RMSE delta
    "als": {"factor_rel": 5e-2, "rmse_rel": 2e-2},
}


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One fit's resolved compute-precision policy.

    ``name`` is the resolved tier (never ``auto``); ``requested`` is what
    config asked for (``auto`` preserved, for summaries/debugging).
    ``input_dtype``/``accum_dtype`` are numpy dtype NAMES (hashable, so a
    policy can ride static jit args); ``dot_tier`` is the legacy
    ``matmul_precision`` tier the f32 dots run at.
    """

    name: str
    requested: str
    input_dtype: str
    accum_dtype: str
    dot_tier: str


def check_tier(name: str) -> str:
    """Validate a resolved tier name (ops-level entry guard): a typo'd
    policy string must raise, never silently run f32 (the
    kmeans_kernel/als_kernel config contract)."""
    if name not in TIERS:
        raise ValueError(
            f"compute_precision tier must be one of {TIERS}, got {name!r}"
        )
    return name


def _check_choice(field: str, value: str) -> str:
    if value not in CHOICES:
        raise ValueError(
            f"{field} must be one of {CHOICES} (empty inherits "
            f"compute_precision for the per-algorithm overrides), got "
            f"{value!r}"
        )
    return value


def legacy_precision(tier: str):
    """Map a ``matmul_precision`` tier to a ``lax.Precision`` (the same
    table as kmeans_ops._prec / pca_ops._cov_prec; duplicated here so
    the policy layer has no import cycle with the ops it serves).
    Unknown values raise — a typo must not silently degrade to bf16."""
    from jax import lax

    try:
        return {
            "highest": lax.Precision.HIGHEST,
            "high": lax.Precision.HIGH,
            "default": lax.Precision.DEFAULT,
        }[tier]
    except KeyError:
        raise ValueError(
            "matmul_precision must be 'highest', 'high', or 'default', "
            f"got {tier!r}"
        ) from None


def _fast_bf16_backend() -> bool:
    """Does the backend have native bf16 matmul units?  TPUs do (the MXU
    is bf16-first); CPU gets no throughput from bf16 casts (jax emulates
    them), so ``auto`` stays f32 there — explicit ``bf16`` still works
    everywhere (parity tests run it on CPU)."""
    import jax

    return jax.default_backend() == "tpu"


# -- thread-local attempt tracking (the resilience ladder's view) ------------

_tls = threading.local()


def begin_attempt() -> None:
    """Reset the resolved-policy record for one fit attempt
    (utils/resilience.resilient_fit calls this before each attempt so
    :func:`reduced_active` reflects only the attempt that faulted)."""
    _tls.resolved = []


def reduced_active() -> bool:
    """Did the current attempt resolve any reduced-precision policy?
    The resilience ladder steps its ``precision`` rung (retry at f32)
    only when this is true — a fit already at f32 must keep the exact
    pre-policy fault semantics."""
    return any(p != "f32" for p in getattr(_tls, "resolved", []))


def forcing_f32() -> bool:
    return bool(getattr(_tls, "force_f32", False))


@contextlib.contextmanager
def force_f32():
    """Scope in which :func:`resolve` pins every policy to f32 — the
    resilience ladder's ``precision`` degradation rung."""
    prev = getattr(_tls, "force_f32", False)
    _tls.force_f32 = True
    try:
        yield
    finally:
        _tls.force_f32 = prev


# -- resolution ---------------------------------------------------------------


def resolve(algo: str, cfg=None) -> PrecisionPolicy:
    """The per-fit policy for ``algo`` ("kmeans" | "pca" | "als").

    Order: per-algorithm override (``<algo>_precision``, empty inherits)
    -> ``compute_precision`` -> ``auto`` resolution (bf16 iff a parity
    bound is registered AND the backend has fast bf16) -> pins: x64
    fits stay f32 (no bf16 fast path for f64), and an active
    :func:`force_f32` scope (the resilience ladder's precision rung)
    overrides everything.  Validates ``matmul_precision`` too, so a
    typo'd tier raises at fit entry on every policy — not only when the
    f32 dots would have read it."""
    if algo not in ALGOS:
        raise ValueError(f"unknown algorithm {algo!r}; expected one of {ALGOS}")
    cfg = cfg or get_config()
    legacy_precision(cfg.matmul_precision)  # typo'd tier fails fast
    requested = _check_choice(
        "compute_precision", cfg.compute_precision
    )
    override = {
        "kmeans": cfg.kmeans_precision,
        "pca": cfg.pca_precision,
        "als": cfg.als_precision,
    }[algo]
    if override:
        requested = _check_choice(f"{algo}_precision", override)
    if forcing_f32():
        name = "f32"
    elif requested == "auto":
        name = (
            "bf16"
            if algo in PARITY_BOUNDS
            and not cfg.enable_x64
            and _fast_bf16_backend()
            else "f32"
        )
    elif cfg.enable_x64:
        # the x64 parity lane always wins: reduced precision under f64
        # would silently break the bit-level reference contract
        name = "f32"
    else:
        name = requested
    if cfg.enable_x64:
        in_dt = acc_dt = "float64"
    elif name == "bf16":
        in_dt, acc_dt = "bfloat16", "float32"
    else:
        in_dt = acc_dt = "float32"
    dot_tier = {
        "f32": cfg.matmul_precision, "tf32": "high", "bf16": "default"
    }[name]
    policy = PrecisionPolicy(
        name=name, requested=requested, input_dtype=in_dt,
        accum_dtype=acc_dt, dot_tier=dot_tier,
    )
    resolved = getattr(_tls, "resolved", None)
    if resolved is None:
        resolved = _tls.resolved = []
    resolved.append(name)
    return policy


def kernel_tier(name: str, matmul_tier: str) -> str:
    """The legacy K-Means/PCA kernel-tier string a policy maps onto
    (the Pallas mode and the XLA Lloyd/Gram ``precision`` argument):
    f32 keeps the configured ``matmul_precision``, tf32 is the bf16_3x
    "high" tier, bf16 the single-pass "default" tier.  One mapping so
    the kernel-dispatch rules (``pallas_preferred``) price a policy
    exactly like the tier it runs at."""
    check_tier(name)
    return {"f32": matmul_tier, "tf32": "high", "bf16": "default"}[name]


def is_reduced_dtype(dtype) -> bool:
    """Is ``dtype`` a reduced-precision tier under the policy (bf16/f16)?
    Shared vocabulary for the collective sanitizer's payload fingerprints
    (utils/sanitizers.py tags reduced payloads so a cross-rank POLICY
    divergence — one rank staging bf16 while another stages f32 — shows
    up in the fingerprint) and for oaplint R18's runtime counterpart."""
    try:
        name = str(np.dtype(dtype))
    except TypeError:
        name = str(dtype)
    return name in ("bfloat16", "float16")


# -- staging-time casts -------------------------------------------------------


def staging_dtype(name: str, base_dtype) -> np.dtype:
    """The numpy dtype streamed chunks are STAGED at under a policy: bf16
    halves the host pad/convert output and the host->device transfer
    bytes (the prefetch pipeline stages chunks in this dtype, so the
    reduction applies before the wire, not after).  f32/tf32 (and any
    f64 lane) keep the accumulation dtype — bit-compatible staging."""
    check_tier(name)
    if name == "bf16" and np.dtype(base_dtype) == np.float32:
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(base_dtype)


# -- policy-aware dots --------------------------------------------------------


def upcast(x):
    """bf16 -> f32 view for VPU reductions (squared norms, centering):
    the values already carry bf16 rounding, but the REDUCTION must
    accumulate in f32 — summing squares in bf16 loses whole rows at
    realistic d.  No-op (bit-compatible) for f32/f64 inputs."""
    import jax.numpy as jnp

    return x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x


def _is_f64(*ops) -> bool:
    return any(np.dtype(o.dtype) == np.float64 for o in ops)


def pdot(a, b, policy: str = "f32", tier: str = "highest"):
    """``a @ b`` under a policy, always accumulating in f32 (f64 on the
    x64 lane):

    - ``bf16``: both operands cast to bfloat16 (no-op when staging
      already delivered bf16) with ``preferred_element_type=f32`` — the
      MXU's native mode, half the operand HBM bytes;
    - ``tf32``: ``lax.Precision.HIGH`` (bf16_3x) on f32 operands;
    - ``f32``: the legacy ``tier`` — bit-compatible with the
      pre-policy call sites.

    f64 operands always run full precision (policy resolution pins x64
    fits to f32, so this is a defensive invariant, not a path)."""
    import jax.numpy as jnp
    from jax import lax

    check_tier(policy)
    if policy == "bf16" and not _is_f64(a, b):
        return jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    prec = (
        lax.Precision.HIGH if policy == "tf32" and not _is_f64(a, b)
        else legacy_precision(tier)
    )
    return jnp.matmul(upcast(a), upcast(b), precision=prec)


def peinsum(subscripts: str, a, b, policy: str = "f32"):
    """Two-operand einsum under a policy — the ALS normal-equation
    moment kernels' entry (they ran HIGHEST unconditionally before the
    policy existed, so the f32 policy keeps HIGHEST: bit-compatible).
    bf16 casts both operands and accumulates f32; tf32 runs bf16_3x."""
    import jax.numpy as jnp
    from jax import lax

    check_tier(policy)
    if policy == "bf16" and not _is_f64(a, b):
        return jnp.einsum(
            subscripts, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    prec = (
        lax.Precision.HIGH if policy == "tf32" and not _is_f64(a, b)
        else lax.Precision.HIGHEST
    )
    return jnp.einsum(subscripts, upcast(a), upcast(b), precision=prec)


# -- summary/telemetry plumbing ----------------------------------------------


def record(summary, timings, policy: PrecisionPolicy) -> None:
    """Stamp the chosen policy on a fit: dict summaries (PCA/ALS) get a
    ``"precision"`` key, object summaries (KMeansSummary) a
    ``.precision`` attribute, and the span-tree root an
    ``attrs["precision"]`` entry so the policy rides the telemetry
    exporters (JSONL sink, ``telemetry.report``) next to the phase
    walls."""
    if summary is not None:
        if isinstance(summary, dict):
            summary["precision"] = policy.name
        else:
            summary.precision = policy.name
    if timings is not None:
        timings.root.attrs["precision"] = policy.name
