"""PCA through the public entry ``oap_mllib_tpu.PCA(k).fit(x)``.

See ``estimators/kmeans.py`` for what an adapter gives the harness.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REFERENCE = "pca_ref"
GEN_BLOCK_ROWS = 4096  # 8 MB a block: filled, scaled and turned in cache
GEN_THREADS = max(1, min(12, len(os.sched_getaffinity(0))))


def make_data(cfg, rows, seed):
    """``rows`` x d float32, C-contiguous, on the host: N(0,1) rows scaled by
    a decaying spectrum, turned by a random Householder reflection (a dense
    orthogonal basis that costs O(d) a row, not O(d^2)), shifted off-centre.
    As ``chip_smoke._decaying``: well separated top eigenvectors, a mean
    that is not negligible."""
    d, p = cfg["d"], cfg["data"]
    root = np.random.SeedSequence([int(seed), 0xDECA1])
    n_blocks = -(-rows // GEN_BLOCK_ROWS)
    seeds = root.spawn(n_blocks + 1)
    head = np.random.default_rng(seeds[0])
    scales = (
        p["top"] * p["decay"] ** -np.arange(d, dtype=np.float64) + p["floor"]
    ).astype(np.float32)
    mirrors = head.standard_normal((p["reflections"], d))
    mirrors = (mirrors / np.linalg.norm(mirrors, axis=1, keepdims=True)).astype(np.float32)
    shift = (head.standard_normal(d) * p["shift"]).astype(np.float32)
    x = np.empty((rows, d), dtype=np.float32)

    def fill(i):
        rng = np.random.default_rng(seeds[i + 1])
        xb = x[i * GEN_BLOCK_ROWS:(i + 1) * GEN_BLOCK_ROWS]
        rng.standard_normal(out=xb, dtype=np.float32)
        xb *= scales
        for u in mirrors:
            t = xb @ u
            t *= -2.0
            for lo in range(0, xb.shape[0], 1024):
                xb[lo:lo + 1024] += t[lo:lo + 1024, None] * u[None, :]
        xb += shift

    with ThreadPoolExecutor(GEN_THREADS) as pool:
        list(pool.map(fill, range(n_blocks)))
    return x


def program_settings(cfg):
    return dict(
        cfg["program_config"], matmul_precision=cfg["matmul_precision"],
        pca_solver=cfg["pca_solver"],
    )


def fit(cfg, x, seed):
    """One whole fit (PCA takes no seed); returns (what the model says, what
    the summary says).  The fit ends when the arrays are on the host."""
    from oap_mllib_tpu import PCA

    model = PCA(k=cfg["k"]).fit(x)
    s = model.summary
    result = {
        "components": np.array(model.components_),
        "ratios": np.array(model.explained_variance_),
    }
    info = {
        "phases": dict(s["timings"].as_dict()),
        "kernel": s.get("kernel"),
        "accelerated": bool(s.get("accelerated", False)),
        "resilience": dict(s.get("resilience") or {}),
    }
    return result, info


# -- the work a fit requires ---------------------------------------------------
# Moments of n rows of d features (column sums and the Gram matrix can share
# one pass):  2*n*d^2 + n*d operations, 4*n*d bytes read once.
# Symmetric eigensolve of the d x d covariance: 9*d^3 (tridiagonalisation
# 4/3 d^3, QR iteration with vectors ~6 d^3, back-transformation 2 d^3;
# Golub & Van Loan).  Noise beside the moments at d = 512.


def moments_work(n, d):
    return {"flops": 2.0 * n * d * d + 1.0 * n * d, "bytes": 4.0 * n * d}


def eigh_work(d):
    return {"flops": 9.0 * d ** 3, "bytes": 4.0 * d * d}


def phase_work(cfg, rows, info):
    return {
        "covariance": moments_work(rows, cfg["d"]),
        "eigh": eigh_work(cfg["d"]),
    }


def fit_work(cfg, rows, info):
    w = phase_work(cfg, rows, info)
    return {
        "flops": sum(v["flops"] for v in w.values()),
        "bytes": sum(v["bytes"] for v in w.values()),
    }
