"""The plain K-Means reference for a table handed over as float64 rows on
no row bucket (``configs/kmeans_d256_k1000_f64rows.json``).

Imports nothing of the program and takes nothing the program made.  What
the configuration guarantees of staging is stated here in the plainest
way: the table the fit sees is the caller's, each value rounded to float32
once, to nearest (NumPy's ``astype``, block by block so that no second
float64-sized array is made) — exactly its rows, each once, and no other
row.  From there on it is ``kmeans_ref``'s arithmetic (the same ``judge``
and ``fit_plain``, at ``highest``, float64 sums on the host) over EXACTLY
those rows: ``kmeans_ref`` walks its table in blocks of 32,768 rows and
refuses a count they do not divide (3,125,000 is 12,207 * 256 + 8), so its
``upload`` is put aside for this file's, which cuts the rows into the
largest equal blocks of at most that many (100 of 31,250 here).
"""

import numpy as np

from reference import kmeans_ref

ROUND_BLOCK_ROWS = 1 << 16


def rounded(x_host, dtype=np.float32):
    """``x_host.astype(dtype)`` in row blocks; ``x_host`` itself where it
    has the dtype already."""
    if x_host.dtype == dtype:
        return x_host
    out = np.empty(x_host.shape, dtype)
    for lo in range(0, x_host.shape[0], ROUND_BLOCK_ROWS):
        out[lo:lo + ROUND_BLOCK_ROWS] = x_host[lo:lo + ROUND_BLOCK_ROWS].astype(dtype)
    return out


def block_rows(n):
    """The largest divisor of ``n`` that is at most ``kmeans_ref.BLOCK_ROWS``:
    every row is in exactly one block and no block is padded."""
    return max(b for b in range(1, min(kmeans_ref.BLOCK_ROWS, n) + 1) if n % b == 0)


def upload(x_host):
    """The host table as (blocks, block_rows, d) on the device."""
    import jax

    b = block_rows(x_host.shape[0])
    return jax.device_put(x_host.reshape(x_host.shape[0] // b, b, x_host.shape[1]))


def _over_exact_rows(fn, x_host, cfg, *args):
    """``kmeans_ref.<fn>`` on the table as the configuration's ``dtype``
    states it, with this file's blocking in place of ``kmeans_ref``'s."""
    x = rounded(x_host, np.dtype(cfg.get("dtype", "float32")))
    theirs = kmeans_ref.upload
    kmeans_ref.upload = upload
    try:
        return fn(x, cfg, *args)
    finally:
        kmeans_ref.upload = theirs


def judge(x_host, cfg, results, seed):
    """``kmeans_ref.judge``'s numbers (``cost_gap``, ``size_gap``,
    ``step_gap``, ``count_gap``, ``shape_gap``) over the rounded table's
    own rows: ``count_gap`` is 0 only if the sizes sum to exactly
    ``x_host.shape[0]``, so a pad row counted, or a row dropped, shows."""
    return _over_exact_rows(kmeans_ref.judge, x_host, cfg, results, seed)


def fit_plain(x_host, cfg, seed, precision="highest"):
    """``kmeans_ref.fit_plain`` on the rounded table (the controls put it in
    the program's place)."""
    return _over_exact_rows(kmeans_ref.fit_plain, x_host, cfg, seed, precision)
