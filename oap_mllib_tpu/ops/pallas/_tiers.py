"""Shared plumbing for the Pallas kernel plane: precision tiers, padding
arithmetic, and per-kernel telemetry booking.

Every kernel in ``ops/pallas/`` exposes the same three precision tiers —
Mosaic only lowers ``Precision.HIGHEST``/``DEFAULT`` on the MXU, so the
intermediate "high" tier is hand-rolled from bf16 hi/lo splits (the
``kmeans_kernel`` pattern, generalized here so the PCA Gram and ALS
normal-equation kernels cannot drift from it):

- ``highest``: full-f32 ``Precision.HIGHEST`` dots — the parity tier.
- ``high``: bf16_3x-equivalent — operands split into bf16 hi+lo pairs
  and recombined from the three significant cross passes (hi*hi, hi*lo,
  lo*hi; lo*lo is below f32 resolution), ~1e-5 of full f32 at 3/6 the
  MXU passes.
- ``default``: single-pass all-bf16 with f32 accumulation — the XLA
  default tier's ~1e-3 envelope at its speed.

The compute-precision policy names (utils/precision.py) alias onto the
tiers — ``f32``→highest, ``tf32``→high, ``bf16``→default — so a resolved
policy can be passed straight through (:func:`check_mode`), which is what
lets ``precision.kernel_tier`` price the bf16 policy ON Pallas.

Telemetry: :func:`kernel_launch` books every kernel-wrapper dispatch into
the process metrics registry (``oap_kernel_launches_total{kernel=}`` +
``oap_kernel_dispatch_seconds``) and notes it on the active span, so fits
report which Pallas kernels ran next to their phase walls.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "default")
# compute-precision policy names (utils/precision.py) accepted as mode
# aliases: the kernels' tiers already ARE the policy's hand-rolled bf16
# splits, so callers resolving a policy can pass its name straight through
MODE_ALIASES = {"f32": "highest", "tf32": "high", "bf16": "default"}

LANE = 128  # TPU minor-axis tile (f32 lane multiple)
SUBLANE = 8  # f32 second-minor tile: DMA windows come in whole eights

# Scoped-VMEM ceiling handed to Mosaic for the kernels whose resident
# blocks grow with the problem (K-Means centers+sums, the PCA Gram).  The
# compiler's default is 16 MiB of the v5e core's 128 MiB; the (k, d) and
# (d, d) accumulators plus their matmul temporaries pass that from
# k=2048, d=256 and d=2048 on.  The cap is not an allocation — a kernel
# is given what it uses — and it is what the dispatch bounds
# (kmeans_ops.pallas_preferred, pca_kernel.pallas_gram_preferred) were
# fitted against: tests/test_tpu_compile.py compiles their edges.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def compiled_kwargs(interpret: bool, **params):
    """The ``compiler_params`` keyword of a compiled ``pallas_call``
    launch; nothing under the interpreter, which takes none.  The
    kernels whose resident blocks grow with the problem pass
    ``vmem_limit_bytes=VMEM_LIMIT_BYTES``; the ALS walks, whose blocks
    are fixed by rank and batch, stay inside Mosaic's default."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(**params)}


def check_mode(mode: str) -> str:
    """Canonicalize a tier: legacy names pass through, policy names map
    via :data:`MODE_ALIASES`, anything else raises (a typo must not
    silently run a different tier)."""
    mode = MODE_ALIASES.get(mode, mode)
    if mode not in MODES:
        raise ValueError(
            f"mode must be one of {MODES} (or a policy alias "
            f"{tuple(MODE_ALIASES)}), got {mode!r}"
        )
    return mode


def pad_to(x: int, m: int) -> int:
    """Round ``x`` up to a multiple of ``m``."""
    return ((x + m - 1) // m) * m


def split_bf16(a):
    """f32 -> (hi, lo) bf16 pair with a ~= hi + lo."""
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def split3_bf16(a):
    """f32 -> (hi, mid, lo) bf16 triple with a == hi + mid + lo EXACTLY:
    24 significand bits are three times bf16's 8, bf16 has f32's
    exponent range, and both residuals are taken in f32 (where they are
    exact; below 2^-103 a value's last bits are subnormal and a backend
    that flushes those loses them, at most 2^-126 absolute).  A product
    of the triple with an operand that is exact in bf16 (a 0/1 one-hot)
    therefore equals the f32 product in three single passes, no term
    dropped.  For kernels (Mosaic rounds as written) and the CPU: a
    program XLA:TPU compiles keeps excess precision across the convert
    to bf16 and back, so there ``mid`` and ``lo`` come out zero."""
    hi = a.astype(jnp.bfloat16)
    mid, lo = split_bf16(a - hi.astype(jnp.float32))
    return hi, mid, lo


def dot_f32(a, b, dn):
    return jax.lax.dot_general(
        a, b, dimension_numbers=dn,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def dot_bf16(a, b, dn):
    return jax.lax.dot_general(
        a, b, dimension_numbers=dn, preferred_element_type=jnp.float32
    )


def tiered_dot(a, b, dn, mode: str):
    """``dot_general(a, b)`` at a kernel tier, f32 accumulation always.

    ``high`` is the hand-rolled bf16_3x: both operands hi/lo-split, the
    lo*lo pass dropped (it is below f32 resolution for operands whose
    magnitudes the hi parts carry).  Operand order inside the sum runs
    hi*hi + hi*lo + lo*hi so every kernel using this helper shares one
    summation order.
    """
    if mode == "highest":
        return dot_f32(a, b, dn)
    if mode == "default":
        return dot_bf16(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dn)
    a_hi, a_lo = split_bf16(a)
    b_hi, b_lo = split_bf16(b)
    return (
        dot_bf16(a_hi, b_hi, dn)
        + dot_bf16(a_hi, b_lo, dn)
        + dot_bf16(a_lo, b_hi, dn)
    )


def note_emitted(kernel: str) -> None:
    """Trace-time census of Pallas kernels emitted INTO compiled programs
    (the collective facade's ``oap_collective_emitted_total`` pattern):
    kernels traced inside an outer jit/scan body cannot book per-dispatch
    telemetry, so they count once per program build instead."""
    from oap_mllib_tpu.telemetry import metrics as _tm

    _tm.counter(
        "oap_kernel_emitted_total", {"kernel": kernel},
        help="Pallas kernels emitted into compiled programs "
             "(trace-time census, not a dispatch count)",
    ).inc()


@contextlib.contextmanager
def kernel_launch(kernel: str):
    """Book one Pallas-kernel wrapper dispatch: invocation count + wall
    into the metrics registry, plus a note on the active span (the same
    pattern as the collective facade's ``_instrumented``).  The wall is
    dispatch time — trace + compile on a first shape, async dispatch
    after — not device occupancy (the profiler trace layer owns that)."""
    from oap_mllib_tpu.telemetry import metrics as _tm
    from oap_mllib_tpu.telemetry.spans import current_span
    from oap_mllib_tpu.utils.timing import tick

    elapsed = tick()
    try:
        yield
    finally:
        dt = elapsed()
        lab = {"kernel": kernel}
        _tm.counter(
            "oap_kernel_launches_total", lab,
            help="Pallas kernel wrapper dispatches by kernel",
        ).inc()
        _tm.histogram(
            "oap_kernel_dispatch_seconds", lab,
            help="Per-dispatch wall of Pallas kernel wrappers "
                 "(compile included on first shape)",
        ).observe(dt)
        sp = current_span()
        if sp is not None:
            sp.attrs.setdefault("kernels", {})
            sp.attrs["kernels"][kernel] = (
                sp.attrs["kernels"].get(kernel, 0) + 1
            )
