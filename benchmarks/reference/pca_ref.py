"""Plain PCA: the semantics of ``fallback/pca_np.py`` (eigenvectors of the
sample covariance, ratios over the TOTAL variance), computed so that a
4M-row table costs seconds.

Imports nothing of the program and takes nothing the program made.  Two uses:

- ``judge``: row blocks go to the device, where the moments of each block
  about a pivot are taken at ``highest``; the blocks' moments are added up in
  float64 on the host and the spectrum is ``numpy.linalg.eigh`` in float64.
- ``fit_plain``: a whole fit of its own in one precision throughout (block
  moments accumulated in float32 on the device, ``jnp.linalg.eigh`` under
  that matmul precision).  Put in the program's place at a lower precision
  it is the control that has to come out not correct.

Precisions: see ``precision.py``; under ``bfloat16`` the table blocks, the
block moments, the covariance, the components and the ratios are stored in
bfloat16.
"""

import functools

import numpy as np

from reference.precision import matmul, stored

BLOCK_ROWS = 16384


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


@functools.lru_cache(maxsize=None)
def _block_moments(precision):
    jax, jnp = _jax()
    mm = matmul(precision)

    @jax.jit
    def moments(xb, pivot):
        z = stored(stored(xb, precision) - pivot[None, :], precision)
        return (
            stored(jnp.sum(z, axis=0), precision),
            stored(jnp.matmul(z.T, z, precision=mm), precision),
        )

    return moments


def _row_blocks(x_host):
    n = x_host.shape[0]
    b = min(BLOCK_ROWS, n)
    for lo in range(0, n, b):
        yield x_host[lo:lo + b]


def covariance64(x_host):
    """Sample covariance in float64 from per-block float32 moments about a
    pivot (the first block's mean), so that no entry is a small difference
    of large sums."""
    jax, jnp = _jax()
    moments = _block_moments("highest")
    n, d = x_host.shape
    pivot = np.mean(x_host[: min(BLOCK_ROWS, n)], axis=0, dtype=np.float64)
    pivot_dev = jnp.asarray(pivot.astype(x_host.dtype))
    pivot = np.asarray(pivot_dev, dtype=np.float64)
    s = np.zeros(d)
    g = np.zeros((d, d))
    pending = []
    for xb in _row_blocks(x_host):
        pending.append(moments(jax.device_put(xb), pivot_dev))
        if len(pending) > 2:  # keep two blocks in flight, no more
            sb, gb = pending.pop(0)
            s += np.asarray(sb, dtype=np.float64)
            g += np.asarray(gb, dtype=np.float64)
    for sb, gb in pending:
        s += np.asarray(sb, dtype=np.float64)
        g += np.asarray(gb, dtype=np.float64)
    cov = (g - np.outer(s, s) / n) / max(n - 1, 1)
    return cov, pivot + s / n


def spectrum64(cov, k):
    """(the top k explained-variance ratios, all eigenvalues descending)."""
    vals = np.linalg.eigvalsh(cov)[::-1]
    return vals[:k] / vals.sum(), vals


def fit_plain(x_host, cfg, seed=0, precision="highest"):
    """Colsum, centred Gram and eigensolve in float32 at ``precision``."""
    jax, jnp = _jax()
    moments = _block_moments(precision)
    n, d = x_host.shape
    k = cfg["k"]
    with jax.default_matmul_precision(matmul(precision)):
        pivot = stored(
            jnp.mean(jnp.asarray(x_host[: min(BLOCK_ROWS, n)]), axis=0), precision
        )
        s = jnp.zeros((d,), x_host.dtype)
        g = jnp.zeros((d, d), x_host.dtype)
        for xb in _row_blocks(x_host):
            sb, gb = moments(jax.device_put(xb), pivot)
            s, g = s + sb, g + gb
        cov = stored((g - jnp.outer(s, s) / n) / max(n - 1, 1), precision)
        vals, vecs = jnp.linalg.eigh(cov)
        ratios = stored(vals[::-1][:k] / jnp.sum(vals), precision)
        vecs = stored(vecs[:, ::-1][:, :k], precision)
    return {
        "components": np.ascontiguousarray(np.asarray(vecs)),
        "ratios": np.asarray(ratios),
    }


def gaps(components, ratios, ref_ratios, cov, vals):
    """How far one fit's answer lies from the float64 spectrum.

    - ``ratio_gap``: worst relative deviation of an explained-variance ratio.
    - ``residual_gap``: worst ||C v - (v'Cv) v|| / ||C|| over the returned
      components v, normalised: how far each is from being an eigenvector of
      the float64 covariance, whatever the gap to its neighbours.  (The
      angle to the float64 eigenvector is not compared: the XLA:TPU
      eigensolve leaves 1e-6 ... 1.6e-3 rad on the tenth component from table
      to table, more than a bfloat16 computation adds; PERF.md, section 2.)
    """
    v = np.asarray(components, dtype=np.float64)
    r = np.asarray(ratios, dtype=np.float64)
    k = ref_ratios.shape[0]
    if v.shape != (cov.shape[0], k) or r.shape != (k,) or not (
        np.all(np.isfinite(v)) and np.all(np.isfinite(r))
    ):
        return None
    unit = v / np.linalg.norm(v, axis=0, keepdims=True)
    cv = cov @ unit
    rq = np.sum(unit * cv, axis=0)
    return {
        "ratio_gap": float(np.max(np.abs(r - ref_ratios) / ref_ratios)),
        "residual_gap": float(
            np.max(np.linalg.norm(cv - unit * rq, axis=0)) / vals[0]
        ),
    }


def judge(x_host, cfg, results, seed):
    """The numbers that decide ``correct`` for the fits of one window: the
    worst fit's gaps, and ``shape_gap`` 1 where an answer is malformed."""
    cov, _ = covariance64(x_host)
    ref_ratios, vals = spectrum64(cov, cfg["k"])
    worst = {"ratio_gap": 0.0, "residual_gap": 0.0, "shape_gap": 0.0}
    for r in results:
        got = gaps(r["components"], r["ratios"], ref_ratios, cov, vals)
        if got is None:
            worst["shape_gap"] = 1.0
            continue
        for name, val in got.items():
            worst[name] = max(worst[name], val)
    return worst
