"""Plain K-Means: the semantics of ``fallback/kmeans_np.py`` (Lloyd, squared
euclidean distance, empty clusters keep their centre) and of Spark's
k-means|| initialisation, in straightforward ``jax.numpy`` over row blocks.

Imports nothing of the program and takes nothing the program made.  Two uses:

- ``judge``: what a fit returned (centres, cost, cluster sizes) is held
  against this file's own arithmetic on the same table, at ``highest``.
- ``fit_plain``: a whole fit of its own (k-means|| + Lloyd).  Put in the
  program's place it has to come out correct at ``highest`` and not correct
  at a lower precision (the control), or with a fault planted in it.  Its
  cost is NOT a yardstick for a fit's cost: two sound fits from different
  random starts differ by up to 16% here (local optima), a Lloyd loop that
  never ran by 22% (PERF.md, section 2).

Precisions: see ``precision.py``; under ``bfloat16`` the table, the centres,
the cost and the sizes are stored in bfloat16.
"""

import functools

import numpy as np

from reference.precision import matmul, stored

BLOCK_ROWS = 32768


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _blocks(n):
    b = min(BLOCK_ROWS, n)
    if n % b:
        raise ValueError(f"rows {n} are not a multiple of the block {b}")
    return n // b, b


def _nearest(xb, centers, c_sq, precision):
    """Squared distance of each row of the block to its nearest centre, and
    that centre's index (the lowest on a tie).  Not ``jnp.argmin``: on the
    TPU its (value, index) reduction carries the value in bfloat16."""
    _, jnp = _jax()
    x_sq = jnp.sum(xb * xb, axis=1, keepdims=True)
    d2 = x_sq + c_sq[None, :] - 2.0 * jnp.matmul(
        xb, centers.T, precision=precision
    )
    best = jnp.min(d2, axis=1)
    ids = jnp.arange(centers.shape[0], dtype=jnp.int32)
    idx = jnp.min(
        jnp.where(d2 <= best[:, None], ids[None, :], centers.shape[0]), axis=1
    )
    return jnp.maximum(best, 0.0), idx


@functools.lru_cache(maxsize=None)
def _programs(precision):
    jax, jnp = _jax()
    mm = matmul(precision)

    @jax.jit
    def assign_pass(x3, centers):
        """(per-block cost, counts, sums) of one pass at ``centers``."""
        k = centers.shape[0]
        centers = stored(centers, precision)
        c_sq = jnp.sum(centers * centers, axis=1)

        def body(carry, xb):
            counts, sums = carry
            xb = stored(xb, precision)
            best, idx = _nearest(xb, centers, c_sq, mm)
            onehot = (idx[:, None] == jnp.arange(k)[None, :]).astype(xb.dtype)
            counts = counts + jnp.sum(onehot, axis=0).astype(jnp.int32)
            sums = sums + jnp.matmul(onehot.T, xb, precision=mm)
            return (counts, sums), jnp.sum(best)

        init = (jnp.zeros((k,), jnp.int32), jnp.zeros(centers.shape, x3.dtype))
        (counts, sums), costs = jax.lax.scan(body, init, x3)
        return costs, counts, sums

    @jax.jit
    def min_d2(x3, centers, d2_so_far):
        """Row-wise min(d2_so_far, distance to the nearest of ``centers``)."""
        centers = stored(centers, precision)
        c_sq = jnp.sum(centers * centers, axis=1)

        def body(_, args):
            xb, old = args
            best, _ = _nearest(stored(xb, precision), centers, c_sq, mm)
            return None, jnp.minimum(old, best)

        _, out = jax.lax.scan(body, None, (x3, d2_so_far))
        return out

    return assign_pass, min_d2


def _cost(costs):
    return float(np.sum(np.asarray(costs, dtype=np.float64)))


def upload(x_host):
    """The host table as (blocks, block_rows, d) on the device."""
    jax, _ = _jax()
    nb, b = _blocks(x_host.shape[0])
    return jax.device_put(x_host.reshape(nb, b, x_host.shape[1]))


def _weighted_kmeanspp(cand, w, k, rng):
    """k-means++ over weighted candidates, then Lloyd on them (Spark's
    LocalKMeans): float64 on the host, a few thousand points."""
    cand = cand.astype(np.float64)
    n = cand.shape[0]
    sq = np.sum(cand * cand, axis=1)
    centers = np.empty((k, cand.shape[1]))
    centers[0] = cand[rng.choice(n, p=w / w.sum())]
    d2 = np.maximum(sq + centers[0] @ centers[0] - 2.0 * cand @ centers[0], 0.0)
    for j in range(1, k):
        p = w * d2
        total = p.sum()
        pick = rng.choice(n, p=p / total) if total > 0 else rng.integers(n)
        centers[j] = cand[pick]
        d2 = np.minimum(
            d2,
            np.maximum(sq + centers[j] @ centers[j] - 2.0 * cand @ centers[j], 0.0),
        )
    for _ in range(30):
        dist = sq[:, None] + np.sum(centers * centers, 1)[None, :] - 2.0 * cand @ centers.T
        lab = np.argmin(dist, axis=1)
        new = centers.copy()
        tot = np.bincount(lab, weights=w, minlength=k)
        for col in range(cand.shape[1]):
            s = np.bincount(lab, weights=w * cand[:, col], minlength=k)
            new[:, col] = np.where(tot > 0, s / np.maximum(tot, 1e-300), centers[:, col])
        if np.array_equal(new, centers):
            break
        centers = new
    return centers


def _pad_rows(a, quantum):
    """Pad with copies of the first row up to a multiple of ``quantum``, so
    that the compiled shapes do not follow the random candidate count.  A
    copy never changes a minimum, and ties go to the lowest index."""
    pad = (-a.shape[0]) % quantum
    return np.concatenate([a, np.repeat(a[:1], pad, axis=0)]) if pad else a


def init_parallel(x3, x_host, k, seed, steps, precision="highest"):
    """k-means|| (Bahmani et al.; Spark's default): ``steps`` rounds that
    each draw about 2k rows with probability proportional to their squared
    distance, then a weighted k-means++ of the candidates down to k."""
    _, jnp = _jax()
    assign_pass, min_d2 = _programs(precision)
    rng = np.random.default_rng([int(seed), 0x6B6D])
    n = x_host.shape[0]
    picked = [int(rng.integers(n))]
    cand = x_host[picked]
    d2 = jnp.full(x3.shape[:2], jnp.inf, x3.dtype)
    new = cand
    for _ in range(steps):
        d2 = min_d2(x3, jnp.asarray(_pad_rows(new, 3 * k)), d2)
        d2_host = np.asarray(d2, dtype=np.float64).reshape(-1)
        prob = np.minimum(1.0, 2.0 * k * d2_host / d2_host.sum())
        rows = np.nonzero(rng.random(n) < prob)[0]
        if rows.size == 0:
            break
        new = x_host[rows]
        cand = np.concatenate([cand, new])
    if cand.shape[0] <= k:
        extra = rng.choice(n, size=k - cand.shape[0] + 1, replace=False)
        cand = np.concatenate([cand, x_host[extra]])
    _, counts, _ = assign_pass(x3, jnp.asarray(_pad_rows(cand, 6 * k)))
    w = np.asarray(counts, dtype=np.float64)[: cand.shape[0]]
    return _weighted_kmeanspp(cand, w, k, rng).astype(x_host.dtype)


def fit_plain(x_host, cfg, seed, precision="highest"):
    """k-means|| + ``max_iter`` Lloyd iterations + the cost and sizes at the
    returned centres, all at ``precision``."""
    _, jnp = _jax()
    assign_pass, _ = _programs(precision)
    x3 = upload(x_host)
    centers = jnp.asarray(
        init_parallel(x3, x_host, cfg["k"], seed, cfg["init_steps"], precision)
    )
    tol_sq = float(cfg["tol"]) ** 2
    n_iter = 0
    for _ in range(cfg["max_iter"]):
        _, counts, sums = assign_pass(x3, centers)
        col = counts[:, None].astype(sums.dtype)
        new = jnp.where(col > 0, sums / jnp.maximum(col, 1.0), centers)
        new = stored(new, precision)
        moved = float(jnp.max(jnp.sum((new - centers) ** 2, axis=1)))
        centers = new
        n_iter += 1
        if moved <= tol_sq and tol_sq > 0:
            break
    costs, counts, _ = assign_pass(x3, centers)
    return {
        "centers": np.asarray(centers),
        "cost": float(stored(jnp.float32(_cost(costs)), precision)),
        "sizes": np.asarray(
            stored(counts.astype(jnp.float32), precision), dtype=np.int64
        ),
        "num_iter": n_iter,
    }


def judge(x_host, cfg, results, seed):
    """The numbers that decide ``correct`` for the fits of one window.

    - ``cost_gap``: the cost a fit reported against this file's cost of the
      centres it returned, over the whole table (relative).
    - ``size_gap``: the share of rows that the fit's cluster sizes count to
      another centre than this file's assignment at those centres does.
    - ``step_gap``: how much one more Lloyd step of this file, taken from the
      returned centres, still lowers the cost (relative).  After max_iter
      iterations on separated blobs it is small; centres that no Lloyd loop
      has touched (a step that returns its state unchanged) leave a fifth or
      more to gain.
    - ``count_gap``: |sum of the cluster sizes - rows| / rows: every row
      counted once, exactly.
    - ``shape_gap``: 0 where centres are finite and (k, d), the cost finite
      and one size a centre; 1 otherwise (nothing else is then compared).
    The worst fit of the window is reported.
    """
    _, jnp = _jax()
    assign_pass, _ = _programs("highest")
    x3 = upload(x_host)
    n, d = x_host.shape
    worst = {"cost_gap": 0.0, "size_gap": 0.0, "step_gap": 0.0,
             "count_gap": 0.0, "shape_gap": 0.0}
    for r in results:
        c = np.asarray(r["centers"])
        sizes = np.asarray(r["sizes"], dtype=np.int64)
        sound = (
            c.shape == (cfg["k"], d) and bool(np.all(np.isfinite(c)))
            and sizes.shape == (cfg["k"],)
            and 0 <= r["num_iter"] <= cfg["max_iter"]
            and np.isfinite(r["cost"])
        )
        if not sound:
            worst["shape_gap"] = 1.0
            continue
        c_dev = jnp.asarray(c.astype(x_host.dtype))
        costs, counts, sums = assign_pass(x3, c_dev)
        cost_ref = _cost(costs)
        col = counts[:, None].astype(sums.dtype)
        stepped = jnp.where(col > 0, sums / jnp.maximum(col, 1.0), c_dev)
        got = {
            "step_gap": max(0.0, 1.0 - _cost(assign_pass(x3, stepped)[0]) / cost_ref),
            "cost_gap": abs(r["cost"] - cost_ref) / cost_ref,
            "size_gap": float(
                np.abs(sizes - np.asarray(counts, dtype=np.int64)).sum()
            ) / (2.0 * n),
            "count_gap": abs(int(sizes.sum()) - n) / n,
        }
        for name, v in got.items():
            worst[name] = max(worst[name], float(v))
    del x3
    return worst
