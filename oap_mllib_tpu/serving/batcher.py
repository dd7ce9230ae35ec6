"""Request micro-batching: shape-bucketed, progcache-launched scoring.

XLA specializes every program on its input shapes, so a serving plane
answering jittered request sizes would recompile the scoring program
per distinct batch size — seconds of XLA latency injected into random
requests.  This module is the serving half of the compile-amortization
contract (data/bucketing.py + utils/progcache.py): every incoming
batch rounds UP onto the geometric row buckets (padding rows are
sliced back off the result — they are dead weight, never aggregated,
so results are identical to the exact-shape launch), and every scoring
program dispatches through the program-cache registry.  Steady state —
after :func:`~oap_mllib_tpu.serving.registry.ServedModel.warmup` or
one storm through the bucket family — compiles ZERO new XLA programs
(``dev/serve_gate.py`` asserts this against ``xla_compile_count``
ground truth).

Inputs are staged with an EXPLICIT ``jax.device_put`` (serving request
paths stay clean under the ``transfer`` sanitizer's disallow guard)
and the staged buffer is donated to the scoring program off-CPU — the
pad+score+top-k chain reuses the request's own HBM.  Scoring matmuls
route through ``precision.pdot`` under the serving dtype policy
(``Config.serving_precision``; empty inherits the per-algorithm
compute policy — the f32 default is bit-compatible with the direct
model calls).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import numpy as np

from oap_mllib_tpu.config import get_config
from oap_mllib_tpu.data.bucketing import bucket_rows
from oap_mllib_tpu.telemetry import metrics as _tm
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.faults import maybe_fault

# bucket anchor for request row counts: buckets are the x2 geometric
# series over multiples of 8 (vector-lane friendly, and small requests
# round to at most 8 rows of masked padding)
SERVE_ROW_MULTIPLE = 8

_SERVING_CHOICES = ("", "f32", "tf32", "bf16", "auto")


def resolve_policy(algo: str) -> psn.PrecisionPolicy:
    """The serving-time compute policy for ``algo``'s scoring matmuls.

    ``Config.serving_precision`` empty inherits the algorithm's resolved
    compute policy (``precision.resolve`` — so a bf16-fit service scores
    bf16 without a second knob); a non-empty value overrides it with the
    same vocabulary, re-using resolve's auto/x64 pins by resolving
    against a config copy whose global policy is the override.  A typo
    raises at request time (the kmeans_kernel contract).

    The brownout ladder's ``bf16`` rung
    (``traffic.brownout_precision_override``) folds in HERE — but only
    when no explicit ``serving_precision`` pin exists and the algorithm
    has a recorded parity bound: an operator pin always beats a
    degradation rung."""
    cfg = get_config()
    raw = cfg.serving_precision
    if raw not in _SERVING_CHOICES:
        raise ValueError(
            "serving_precision must be one of "
            f"{'|'.join(v or '<empty>' for v in _SERVING_CHOICES)}, "
            f"got {raw!r}"
        )
    if not raw:
        from oap_mllib_tpu.serving import traffic

        browned = traffic.brownout_precision_override(algo)
        if browned:
            return psn.resolve(
                algo,
                dataclasses.replace(
                    cfg, compute_precision=browned,
                    kmeans_precision="", pca_precision="",
                    als_precision="",
                ),
            )
        return psn.resolve(algo)
    return psn.resolve(
        algo,
        dataclasses.replace(
            cfg, compute_precision=raw,
            kmeans_precision="", pca_precision="", als_precision="",
        ),
    )


def bucket_batch(x: np.ndarray,
                 multiple: int = SERVE_ROW_MULTIPLE) -> Tuple[np.ndarray, int]:
    """Round a request batch up to its geometric row bucket.

    Returns ``(padded, n)`` — padded has ``bucket_rows(n)`` rows (zero
    rows appended; every consumer slices the result back to ``n``).
    ``Config.shape_bucketing`` governs the series exactly as it does
    for fits ("off" = exact padding to the multiple)."""
    t0 = time.perf_counter()
    x = np.ascontiguousarray(np.atleast_2d(x))
    n = x.shape[0]
    b = bucket_rows(max(n, 1), multiple)
    if b != n:
        x = np.concatenate(
            [x, np.zeros((b - n, x.shape[1]), x.dtype)], axis=0
        )
    # fold the pad wall into any attached request ledgers (a thread-
    # local miss when no traced flush is in flight — the disarmed seam)
    from oap_mllib_tpu.serving import reqtrace

    reqtrace.note_flush("bucket_pad", time.perf_counter() - t0)
    return x, n


def stage(x: np.ndarray):
    """Explicit host->device staging of one request payload.  Explicit
    (``jax.device_put``) so serving request paths run clean under the
    ``transfer`` sanitizer's disallow guard — any OTHER transfer in the
    hot path is then a caught bug, not noise."""
    import jax

    return jax.device_put(np.asarray(x))


def _donate_args() -> tuple:
    """Donate the staged request buffer to the scoring program — the
    pad/score chain reuses the request's own device memory.  CPU keeps
    buffers (XLA CPU does not implement donation; donating there only
    logs a warning per compile)."""
    import jax

    return (0,) if jax.default_backend() != "cpu" else ()


def _book(kind: str, pad: int) -> None:
    # every scoring batch is a fault-injection site ("serve.request",
    # utils/faults.py) so request-path faults are drillable like every
    # other runtime seam; unarmed, maybe_fault is a dict miss
    maybe_fault("serve.request")
    lab = {"model": kind}
    _tm.counter(
        "oap_serve_batches_total", lab,
        help="Scoring batches launched by the serving plane",
    ).inc()
    _tm.counter(
        "oap_serve_pad_rows_total", lab,
        help="Bucket-padding rows added to serving batches "
             "(masked, sliced off results)",
    ).inc(pad)


# -- scoring programs (one jitted family per op, progcache-registered) --------


def _build_assign(tier: str, policy: str):
    import jax
    import jax.numpy as jnp

    from oap_mllib_tpu.ops import kmeans_ops

    def kernel(xb, centers):
        d2 = kmeans_ops.pairwise_sq_dists(xb, centers, tier, policy)
        return kmeans_ops.argmin_rows(d2).astype(jnp.int32)

    return jax.jit(kernel, donate_argnums=_donate_args())


def assign_kmeans(centers_dev, x: np.ndarray, kind: str = "kmeans"):
    """Bucketed nearest-center assignment: pad ``x`` to its row bucket,
    launch the registry-cached assignment program against the PINNED
    centers, slice ids back to the request rows."""
    import jax

    pol = resolve_policy("kmeans")
    xb, n = bucket_batch(np.asarray(x, dtype=np.dtype(centers_dev.dtype)))
    _book(kind, xb.shape[0] - n)
    fn = progcache.get_or_build(
        "serve.assign",
        (progcache.backend_fingerprint(), pol.name, pol.dot_tier),
        lambda: _build_assign(pol.dot_tier, pol.name),
    )
    staged = stage(xb)
    progcache.note(
        "serve.assign",
        (pol.name, pol.dot_tier,
         progcache.array_key(staged, centers_dev)),
    )
    out = fn(staged, centers_dev)
    return jax.device_get(out)[:n]


def _build_project(tier: str, policy: str):
    import jax

    def kernel(xb, components):
        return psn.pdot(xb, components, policy, tier)

    return jax.jit(kernel, donate_argnums=_donate_args())


def project_pca(components_dev, x: np.ndarray, kind: str = "pca"):
    """Bucketed principal-component projection against the pinned
    (d, k) component matrix (no centering — Spark parity)."""
    import jax

    pol = resolve_policy("pca")
    xb, n = bucket_batch(np.asarray(x, dtype=components_dev.dtype))
    _book(kind, xb.shape[0] - n)
    fn = progcache.get_or_build(
        "serve.project",
        (progcache.backend_fingerprint(), pol.name, pol.dot_tier),
        lambda: _build_project(pol.dot_tier, pol.name),
    )
    staged = stage(xb)
    progcache.note(
        "serve.project",
        (pol.name, pol.dot_tier,
         progcache.array_key(staged, components_dev)),
    )
    out = fn(staged, components_dev)
    return jax.device_get(out)[:n]


def _build_topk(tier: str, policy: str):
    import jax

    def kernel(q, targets, n):
        scores = psn.pdot(q, targets.T, policy, tier)
        return jax.lax.top_k(scores, n)

    return jax.jit(kernel, static_argnames=("n",),
                   donate_argnums=_donate_args())


def topk_pairs(q_dev, targets_dev, n: int, kind: str = "als"):
    """Top-``n`` (scores, ids) per query row against the pinned target
    factors — the serving analog of models/als ``_top_k_pairs``, shared
    by the subset recommenders and the full-sweep chunks.  Returns
    DEVICE arrays (sweep consumers fetch explicitly)."""
    pol = resolve_policy("als")
    fn = progcache.get_or_build(
        "serve.topk",
        (progcache.backend_fingerprint(), pol.name, pol.dot_tier),
        lambda: _build_topk(pol.dot_tier, pol.name),
    )
    progcache.note(
        "serve.topk",
        (pol.name, pol.dot_tier, int(n),
         progcache.array_key(q_dev, targets_dev)),
    )
    return fn(q_dev, targets_dev, int(n))


def topk_scores(query: np.ndarray, targets_dev, n: int,
                kind: str = "als") -> Tuple[np.ndarray, np.ndarray]:
    """Bucketed one-shot top-k for a REQUEST batch of query rows (the
    subset-recommender surface).  The full-user-base sweep lives in
    :mod:`oap_mllib_tpu.serving.sweep` (streamed + sharded)."""
    import jax

    n = min(int(n), int(targets_dev.shape[0]))
    qb, rows = bucket_batch(np.asarray(query, np.float32))
    _book(kind, qb.shape[0] - rows)
    s, i = topk_pairs(stage(qb), targets_dev, n, kind=kind)
    return (
        jax.device_get(i)[:rows].astype(np.int32),
        jax.device_get(s)[:rows],
    )


def warm_sizes(max_rows: int,
               multiple: int = SERVE_ROW_MULTIPLE) -> list:
    """The bucket family covering request sizes up to ``max_rows`` —
    one warmup launch per entry compiles every program a steady-state
    storm of sizes <= max_rows can ever need."""
    out = []
    n = 1
    while True:
        b = bucket_rows(n, multiple)
        if not out or b != out[-1]:
            out.append(b)
        if b >= max_rows:
            break
        n = b + 1
    return out


def xla_snapshot() -> Optional[int]:
    """XLA compile count snapshot helper for gates/benches: the current
    ground-truth backend-compile count (``progcache.xla_compile_count``)
    so callers can assert a ZERO delta across a steady-state storm."""
    return progcache.xla_compile_count()
