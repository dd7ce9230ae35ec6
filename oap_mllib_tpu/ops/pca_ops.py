"""PCA compute kernels: sharded covariance + eigendecomposition.

Replaces the reference's one-shot distributed PCA
(native/PCADALImpl.cpp): there, inputs are mean-centered on the JVM via
StandardScaler (PCADALImpl.scala:101-106), each rank runs oneDAL
``pca::Distributed<step1Local, svdDense>`` (:63-69), serialized partials are
allgatherv'd (:79-113), and the root's step2Master + finalizeCompute yields
eigenvalues/eigenvectors (:122-153).

TPU-first redesign: the covariance of a row-sharded table is two global
reductions — ``sum_i x_i`` and ``X^T X`` (one (d,n)x(n,d) MXU matmul) —
which GSPMD lowers to psums over the data axis; then
``cov = (Gram - n * mu mu^T) / (n - 1)`` and a replicated d x d ``eigh``.
One jitted program, no serialization, no master rank.  The d < 65535 guard
(reference PCA.scala:103) carries over as the bound on the replicated d x d.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P
from typing import Tuple
from oap_mllib_tpu.utils import precision as psn
from oap_mllib_tpu.parallel import collective
from oap_mllib_tpu.utils import progcache
from oap_mllib_tpu.utils.jax_compat import shard_map


def _cov_prec(precision: str):
    """Map the config tier to the Gram matmul precision.  Unknown values
    raise — a typo must not silently degrade to bf16."""
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


@functools.partial(jax.jit, static_argnames=("precision", "policy"))
def _covariance_jit(
    x: jax.Array, mask: jax.Array, n_rows: jax.Array,
    precision: str = "highest", policy: str = "f32",
) -> Tuple[jax.Array, jax.Array]:
    """Sample covariance (d, d) and mean (d,) of the valid rows.

    ``mask`` zeroes padded rows so they drop out of both reductions.
    Two-pass MEAN-CENTERED form at every tier: the one-pass raw-moment
    form ``(X^T X - n mu mu^T) / (n - 1)`` cancels catastrophically for
    large-mean data — measured 4.6e-3 relative at f32-HIGHEST with
    mean=50, unit-variance data (v5e, round 3), outside the 1e-4 parity
    bar — while the centered Gram has no cancellation (1.2e-5 even at
    bf16_3x on the same data).  Centering first also mirrors the
    reference, which runs StandardScaler(withMean) before its kernel
    (PCADALImpl.scala:101-106).  ``precision`` sets the Gram matmul tier
    ("highest" = full f32, the parity contract; "high" = bf16_3x ~2x
    faster within ~1e-5; "default" = bf16, ~1e-4).
    """
    xf = psn.upcast(x)  # colsum/centering reduce in f32 whatever the
    xm = xf * mask[:, None]  # input dtype (no-op for f32/f64 — bit-compat)
    total = jnp.sum(xm, axis=0)  # psum over data axis
    mean = total / n_rows
    xc = (xf - mean[None, :]) * mask[:, None]
    # policy-aware Gram (utils/precision.py): bf16 casts the centered
    # chunk — centering happened in f32 first, so the cast rounds ONCE —
    # and accumulates f32; f32 keeps the legacy tier bit-for-bit
    gram = psn.pdot(xc.T, xc, policy, precision)  # <- MXU
    cov = gram / jnp.maximum(n_rows - 1.0, 1.0)
    # numerical symmetry guard before eigh
    return 0.5 * (cov + cov.T), mean


def use_pallas_gram(kernel_cfg: str, d: int, precision: str, dtype) -> bool:
    """Single source of truth for the PCA Gram kernel dispatch (in-memory
    AND streamed entries, like kmeans_ops.use_pallas_path): the fused
    Pallas moments kernel runs only when configured/preferred AND its
    preconditions hold — TPU backend, one device, one process, f32.
    ``precision`` here is the kernel tier the policy mapped onto
    (utils/precision.kernel_tier), so the bf16 policy's "default" tier
    prices ON Pallas (the ISSUE 9 workaround retirement)."""
    if kernel_cfg not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"pca_kernel must be auto|xla|pallas, got {kernel_cfg!r}"
        )
    from oap_mllib_tpu.ops.pallas.pca_kernel import pallas_gram_preferred

    want = kernel_cfg == "pallas" or (
        kernel_cfg == "auto" and pallas_gram_preferred(d, precision)
    )
    return (
        want
        and jax.default_backend() == "tpu"
        and len(jax.devices()) == 1
        and jax.process_count() == 1
        and np.dtype(dtype) == np.float32
    )


def covariance(
    x: jax.Array, mask: jax.Array, n_rows: jax.Array,
    precision: str = "highest",
    timings=None, phase: str = "covariance",
    policy: str = "f32",
) -> Tuple[jax.Array, jax.Array]:
    """Registry-tracked entry over :func:`_covariance_jit` (semantics in
    its docstring): the launch is noted with the program-cache registry
    (utils/progcache) and, when ``timings`` is given, its wall is booked
    under ``<phase>/compile`` (first program) or ``<phase>/execute``.
    ``policy`` is the compute-precision policy (utils/precision.py).

    Dispatches to the fused Pallas moments kernel
    (ops/pallas/pca_kernel.covariance_pallas — same two-pass centered
    numerics, no HBM centered temp) when :func:`use_pallas_gram` says so;
    the kernel's tier IS the mapped policy, so ``policy`` needs no
    separate plumbing there."""
    from oap_mllib_tpu.config import get_config

    if use_pallas_gram(
        get_config().pca_kernel, x.shape[1], precision, x.dtype
    ):
        from oap_mllib_tpu.ops.pallas import autotune
        from oap_mllib_tpu.ops.pallas.pca_kernel import covariance_pallas

        geo = autotune.resolve(
            "pca", autotune.shape_bucket(x.shape[1]), precision
        )
        key = (
            progcache.backend_fingerprint(),
            progcache.array_key(x, mask), precision, "pallas",
            geo["tile_rows"], geo["depth"],
        )
        with progcache.launch("pca.covariance_pallas", key, timings, phase):
            return covariance_pallas(
                x, mask, n_rows, mode=precision,
                tile_rows=geo["tile_rows"], depth=geo["depth"],
            )
    key = (
        progcache.backend_fingerprint(),
        progcache.array_key(x, mask),
        precision, policy,
    )
    with progcache.launch("pca.covariance", key, timings, phase):
        return _covariance_jit(x, mask, n_rows, precision, policy)


def _model_sharded_cov_fn(mesh, dax: str, max_: str, precision: str,
                          policy: str = "f32"):
    """Compiled model-sharded covariance program, cached in the
    process-wide program registry (utils/progcache; formerly a private
    functools.lru_cache) per mesh fingerprint — a fresh jit(shard_map)
    closure per fit would retrace/recompile every time."""
    key = (progcache.mesh_fingerprint(mesh), dax, max_, precision, policy)
    return progcache.get_or_build(
        "pca.covariance_model_sharded", key,
        lambda: _build_model_sharded_cov(mesh, dax, max_, precision,
                                         policy),
    )


def _build_model_sharded_cov(mesh, dax: str, max_: str, precision: str,
                             policy: str = "f32"):
    """Build the jitted model-sharded covariance program (cached above).
    Tier semantics match :func:`covariance`: fast tiers center on device
    before the Gram (no raw-moment cancellation amplification)."""

    def tile_program(x_blk, mask_blk, n):
        xf = psn.upcast(x_blk)
        xm = xf * mask_blk[:, None]
        col_sum = collective.psum(jnp.sum(xm, axis=0), dax)  # (d_loc,)
        mean_loc = col_sum / n
        # centered Gram at every tier (see covariance: the raw-moment
        # form cancels catastrophically for large-mean data)
        xc = (xf - mean_loc[None, :]) * mask_blk[:, None]
        xc_full = collective.all_gather(xc, max_, axis=1, tiled=True)  # (n_loc, d)
        gram_rows = collective.psum(
            psn.pdot(xc.T, xc_full, policy, precision), dax
        )  # (d_loc, d)
        cov_rows = gram_rows / jnp.maximum(n - 1.0, 1.0)
        return cov_rows, mean_loc

    sharded = shard_map(
        tile_program,
        mesh=mesh,
        in_specs=(P(dax, max_), P(dax), P()),
        out_specs=(P(max_, None), P(max_)),
        check_vma=False,
    )

    def run(x, mask, n):
        cov, mean = sharded(x, mask, n)
        # numerical symmetry guard before eigh (cross-tile roundoff)
        return 0.5 * (cov + cov.T), mean

    return jax.jit(run)


def covariance_model_sharded(
    x: jax.Array, mask: jax.Array, n_rows: jax.Array, mesh,
    precision: str = "highest",
    timings=None, phase: str = "covariance",
    policy: str = "f32",
) -> Tuple[jax.Array, jax.Array]:
    """Covariance with the (d, d) accumulation sharded over the MODEL axis.

    Mesh-sharded linalg (survey §5): on a (data, model) mesh each device
    holds a (rows/data, d/model) tile.  Per device: all_gather the column
    tiles along the model axis (ICI), one (d_loc, n_loc) x (n_loc, d) MXU
    matmul for this device's Gram ROWS, then psum over the data axis — so
    no device ever materializes more than (d/model, d) of the Gram.  The
    reference cannot shard this dimension at all (oneDAL's step2Master
    holds the full d x d on one node, PCADALImpl.cpp:122-153).

    ``d`` must be a multiple of the model-axis size (callers pad feature
    columns with zeros and demote them with :func:`mark_padded_features`
    before eigh).  Returns (cov (d, d) sharded (model, None), mean (d,)).
    """
    from oap_mllib_tpu.config import get_config

    cfg = get_config()
    # pca_kernel validation must run on EVERY accelerated fit (the
    # covariance/use_pallas_gram invariant): a typo'd value raises here
    # too, even though the model-sharded Gram stays on the shard_map path
    use_pallas_gram(cfg.pca_kernel, x.shape[1], precision, x.dtype)
    fn = _model_sharded_cov_fn(
        mesh, cfg.data_axis, cfg.model_axis, precision, policy
    )
    key = (
        progcache.mesh_fingerprint(mesh),
        progcache.array_key(x, mask), precision, policy,
    )
    with progcache.launch(
        "pca.covariance_model_sharded.run", key, timings, phase
    ):
        return fn(x, mask, n_rows)


@functools.partial(jax.jit, static_argnums=(1,))
def mark_padded_features(cov: jax.Array, d_valid: int) -> jax.Array:
    """Set the diagonal of padded feature dims to -1 so their eigenvalues
    sort strictly BELOW any genuine (>= 0, up to roundoff) eigenvalue.

    Without this, a padded column's zero eigenvalue ties with a genuine
    null-space eigenvalue and eigh may order the padded basis vector into
    the top-k, which would slice to an all-zero component column.  cov is
    block-diagonal afterwards, so genuine eigenvectors keep exact zeros in
    the padded rows.
    """
    d_pad = cov.shape[0]
    idx = jnp.arange(d_valid, d_pad)
    return cov.at[idx, idx].set(-1.0)


@jax.jit
def _eigh_descending_jit(cov: jax.Array) -> Tuple[jax.Array, jax.Array]:
    vals, vecs = jnp.linalg.eigh(cov)  # ascending
    vals = vals[::-1]
    vecs = vecs[:, ::-1]
    return vals, vecs


def eigh_descending(cov: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Eigenvalues (descending) and matching eigenvectors (columns) of a
    symmetric matrix — the finalizeCompute analog (PCADALImpl.cpp:122-153).
    Launches register with the program-cache registry (counters only —
    eigh is the large-d wall and its reuse should show in hit rates).
    """
    progcache.note(
        "pca.eigh",
        (progcache.backend_fingerprint(), progcache.array_key(cov)),
    )
    return _eigh_descending_jit(cov)


@functools.partial(
    jax.jit, static_argnames=("k", "oversample", "iters")
)
def topk_eigh_randomized(
    cov: jax.Array, k: int, oversample: int = 16, iters: int = 8
) -> Tuple[jax.Array, jax.Array]:
    """Top-k eigenpairs of an SPD matrix by randomized subspace
    iteration (Halko/Martinsson/Tropp) — the large-d fast path behind
    ``Config.pca_solver="randomized"``.

    At large d the O(d^3) eigh owns most of the PCA wall while k is
    typically tens; subspace iteration replaces the O(d^3)
    factorization with (2*iters + 2) MXU matmuls of (d, d) x (d, p),
    p = k + oversample, plus a (d, p) QR per iteration and one tiny
    (p, p) eigh.

    Accuracy contract (why this is NOT the default): convergence is
    gap-dependent for values AND vectors — each Ritz value approaches
    its eigenvalue like (lambda_p / lambda_i)^(2*iters), so decaying
    spectra (the practical PCA regime) match eigh to ~1e-4 at the
    defaults, while a near-flat spectrum (isotropic noise; measured on
    a d=2048 Wishart edge, v5e round 4) is biased low by ~5% at the
    defaults, ~0.3% at iters=16/oversample=64 — and its top-k
    eigenVECTORS are genuinely ill-defined, so no iteration count makes
    them match eigh's.  tests/test_pca.py pins both behaviors.

    Deterministic: the probe uses a fixed PRNG key — same cov, same
    result.  Returns (vals (k,) descending, vecs (d, k))."""
    d = cov.shape[0]
    p = min(d, k + oversample)
    probe = jax.random.normal(jax.random.PRNGKey(0), (d, p), cov.dtype)
    q, _ = jnp.linalg.qr(probe)

    def body(q, _):
        y = psn.pdot(cov, q)
        q_next, _ = jnp.linalg.qr(y)  # re-orthonormalize every step
        return q_next, None

    q, _ = lax.scan(body, q, None, length=iters)
    b = psn.pdot(q.T, psn.pdot(cov, q))
    w, v = jnp.linalg.eigh(0.5 * (b + b.T))  # ascending, (p, p)
    w = w[::-1][:k]
    v = v[:, ::-1][:, :k]
    return w, psn.pdot(q, v)


@jax.jit
def project(x: jax.Array, components: jax.Array) -> jax.Array:
    """Transform rows into the component basis: (n, d) @ (d, k).

    NOTE Spark parity: PCAModel.transform does NOT mean-center before
    projecting (mllib.feature.PCAModel), so neither do we.
    """
    return psn.pdot(x, components)
