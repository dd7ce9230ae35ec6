"""K-Means as ``estimators/kmeans.py`` drives it, on the table a Spark
executor really hands over: float64 rows (``ml.linalg.DenseVector`` is
``Array[Double]``) and as many of them as the partitioner gave, on no row
bucket.  The same blobs, drawn in float64 so that every value needs
rounding to the table's float32; ``rows`` as given.

The settings and the work functions are the sibling's.  Required work
counts the VALID rows (``fit_work(cfg, rows, info)`` with the cell's
``rows``): the rows the program pads to its bucket are no required work, so
``fit_mfu_pct`` shows them as waste.  ``fit`` is the sibling's call, and
besides hands the readers what staging said of the upload
(``info["staging"]``: the ``table_convert/upload`` span's attributes and the
``host_copy`` span's ``copied_bytes``; a program that records none of them
leaves them out).
"""

import numpy as np

from estimators.kmeans import (  # noqa: F401  (what an adapter gives the harness)
    GEN_BLOCK_ROWS, GEN_THREADS, cost_pass_work, fit_work, init_work,
    lloyd_iteration_work, phase_work, program_settings,
)

REFERENCE = "kmeans_f64rows_ref"
UPLOAD_SPAN = "table_convert/upload"
HOST_COPY_SPAN = "table_convert/host_copy"


def make_data(cfg, rows, seed):
    """``rows`` x d float64, C-contiguous, on the host: ``estimators/
    kmeans.make_data``'s blobs (k prototypes ~ N(0,1)^d, each row one of
    them plus N(0, spread^2) noise), every number drawn in float64, block
    by block from seeds of their own so that the table does not depend on
    the number of threads."""
    from concurrent.futures import ThreadPoolExecutor

    d, k = cfg["d"], cfg["k"]
    dtype = np.dtype(cfg.get("input_dtype", "float64"))
    spread = dtype.type(cfg["data"]["spread"])
    root = np.random.SeedSequence([int(seed), 0xF64B10B5])
    n_blocks = -(-rows // GEN_BLOCK_ROWS)
    seeds = root.spawn(n_blocks + 1)
    proto = np.random.default_rng(seeds[0]).standard_normal((k, d), dtype=dtype)
    x = np.empty((rows, d), dtype=dtype)

    def fill(i):
        rng = np.random.default_rng(seeds[i + 1])
        xb = x[i * GEN_BLOCK_ROWS:(i + 1) * GEN_BLOCK_ROWS]
        rng.standard_normal(out=xb, dtype=dtype)
        xb *= spread
        xb += proto[rng.integers(k, size=xb.shape[0])]

    with ThreadPoolExecutor(GEN_THREADS) as pool:
        list(pool.map(fill, range(n_blocks)))
    return x


def _staging(timings):
    """What the program's staging spans say of one fit's upload."""
    phases = timings.as_dict()
    out = {}
    if UPLOAD_SPAN in phases:
        out.update(timings.root.node(UPLOAD_SPAN).attrs)
    if HOST_COPY_SPAN in phases:
        out.update(timings.root.node(HOST_COPY_SPAN).attrs)
    return out


def fit(cfg, x, seed):
    """One whole fit, as ``estimators/kmeans.fit`` makes it (the same call,
    the same result and info; that function returns no summary, so the call
    is repeated here), with the staging spans' attributes beside the
    phases."""
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
        "staging": _staging(s.timings),
    }
    return result, info
