"""K-Means through the public entry ``oap_mllib_tpu.KMeans(...).fit(x)``.

An estimator adapter gives the harness five things: the table made from the
seed, the program's settings, one whole fit with what it returned, the plain
reference that judges it, and the work a fit REQUIRES (operations and bytes,
from shapes alone), whatever implements it.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REFERENCE = "kmeans_ref"
GEN_BLOCK_ROWS = 8192  # 8 MB a block: filled, scaled and shifted in cache
GEN_THREADS = max(1, min(12, len(os.sched_getaffinity(0))))


def make_data(cfg, rows, seed):
    """``rows`` x d float32, C-contiguous, on the host: each row one of k
    prototypes ~ N(0,1)^d plus N(0, spread^2) noise (``chip_smoke._blobs``),
    filled block by block from seeds of their own so that threads can share
    the work and the table does not depend on their number.  Most of the
    time is the first touch of the table's pages, which threads share too."""
    d, k = cfg["d"], cfg["k"]
    spread = np.float32(cfg["data"]["spread"])
    root = np.random.SeedSequence([int(seed), 0xB10B5])
    n_blocks = -(-rows // GEN_BLOCK_ROWS)
    seeds = root.spawn(n_blocks + 1)
    proto = np.random.default_rng(seeds[0]).standard_normal((k, d), dtype=np.float32)
    x = np.empty((rows, d), dtype=np.float32)

    def fill(i):
        rng = np.random.default_rng(seeds[i + 1])
        xb = x[i * GEN_BLOCK_ROWS:(i + 1) * GEN_BLOCK_ROWS]
        rng.standard_normal(out=xb, dtype=np.float32)
        xb *= spread
        xb += proto[rng.integers(k, size=xb.shape[0])]

    with ThreadPoolExecutor(GEN_THREADS) as pool:
        list(pool.map(fill, range(n_blocks)))
    return x


def program_settings(cfg):
    return dict(cfg["program_config"], matmul_precision=cfg["matmul_precision"])


def fit(cfg, x, seed):
    """One whole fit; returns (what the model says, what the summary says).
    The fit ends when the model's arrays are on the host."""
    from oap_mllib_tpu import KMeans

    model = KMeans(
        k=cfg["k"], max_iter=cfg["max_iter"], tol=cfg["tol"], seed=seed,
        init_mode=cfg["init_mode"], init_steps=cfg["init_steps"],
    ).fit(x)
    s = model.summary
    result = {
        "centers": np.array(model.cluster_centers_),
        "cost": float(s.training_cost),
        "sizes": np.array(getattr(s, "cluster_sizes", ())),
        "num_iter": int(s.num_iter),
    }
    info = {
        "phases": dict(s.timings.as_dict()),
        "num_iter": int(s.num_iter),
        "kernel": getattr(s, "kernel", None),
        "accelerated": bool(getattr(s, "accelerated", False)),
        "resilience": dict(getattr(s, "resilience", None) or {}),
    }
    return result, info


# -- the work a fit requires (floating-point operations, bytes of HBM) --------
# One Lloyd iteration over n rows, k centres, d features:
#   assignment          2*n*k*d   (the n x k sheet of scalar products)
#   accumulate moments  2*n*d     (each row added to one centre: n*d adds, and
#                                  as many for the counts and the division;
#                                  the one-hot matmul of today's kernel is an
#                                  implementation choice and is not counted)
#   bytes               4*n*d     (the float32 table read once)
# The final cost pass (training_cost at the returned centres) is one more
# assignment.  k-means|| with s steps: each step one assignment against the
# ~2k rows drawn in the step before, and one pass weighting ~2ks candidates:
#   2*n*(2k)*d per step + 2*n*(2ks)*d, reading the table s+1 times.


def lloyd_iteration_work(n, k, d):
    return {"flops": 2.0 * n * k * d + 2.0 * n * d, "bytes": 4.0 * n * d}


def cost_pass_work(n, k, d):
    return {"flops": 2.0 * n * k * d, "bytes": 4.0 * n * d}


def init_work(n, k, d, steps):
    return {
        "flops": 2.0 * n * (2 * k) * d * steps + 2.0 * n * (2 * k * steps) * d,
        "bytes": 4.0 * n * d * (steps + 1),
    }


def _add(*works):
    return {
        "flops": sum(w["flops"] for w in works),
        "bytes": sum(w["bytes"] for w in works),
    }


def _times(work, m):
    return {"flops": work["flops"] * m, "bytes": work["bytes"] * m}


def phase_work(cfg, rows, info):
    """Required work by phase for one fit that ran ``info['num_iter']``
    Lloyd iterations."""
    n, k, d = rows, cfg["k"], cfg["d"]
    return {
        "init_centers": init_work(n, k, d, cfg["init_steps"]),
        "lloyd_loop": _add(
            _times(lloyd_iteration_work(n, k, d), info["num_iter"]),
            cost_pass_work(n, k, d),
        ),
    }


def fit_work(cfg, rows, info):
    return _add(*phase_work(cfg, rows, info).values())
