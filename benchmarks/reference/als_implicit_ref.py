"""Plain implicit-feedback ALS (Hu, Koren, Volinsky 2008) with Spark ML's
conventions, as ``oap_mllib_tpu/models/als.py::_fit_impl`` states them:

- confidence ``1 + alpha * |r|``, so a rating adds ``alpha * |r| * y y^T``
  to its destination's normal matrix beside the Gram ``Y^T Y`` of all
  sources;
- preference 1 only for ``r > 0``: only those add ``(1 + alpha * |r|) * y``
  to the right-hand side (a rating with ``r = 0`` adds nothing to either:
  its confidence is 1 and its preference 0, as for a pair never rated);
- lambda scaled by each row's count of ``r > 0`` ratings; a row without one
  gets the zero vector.

Imports nothing of the program and takes nothing the program made.  The
table is ``(users, items, ratings)`` as handed to the fit.  Written as COO
segment sums: the ratings go to the device once, and a half-update walks
them in blocks of ``BLOCK_EDGES``, gathers the source rows, forms each
rating's moments elementwise in float32 (the upper triangle of the outer
product, the right-hand side and the count: ``WIDTH`` numbers) and
``jax.ops.segment_sum``\\ s them by destination into one float32 sheet; the
Gram is a matmul at the precision asked for; the ``n_dst`` solves of the
half-update that ``judge`` holds every fit to are ``numpy.linalg.solve`` in
float64 on the host, those of the iterations (``fit_plain``, the replay) a
written-out float32 elimination on the device.

``judge`` starts from the SAME initial factors as the program:
``init_factors(cfg, seed)`` makes them from a fit's seed with NumPy, the
adapter hands them to the program through ``init=``, and every result
carries the seed it started from.

Precisions: see ``precision.py``; under ``bfloat16`` the scores, the factors
gathered, the moment sheet and the factors returned are stored in bfloat16.
"""

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference.precision import matmul, stored

BLOCK_EDGES = 1 << 20
SOLVE_CHUNK = 1 << 14  # destinations a host thread solves at a time
SOLVE_THREADS = max(1, min(12, len(os.sched_getaffinity(0))))
REPLAYED = (0, -1)  # the window's fits whose five iterations are replayed


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def init_factors(cfg, seed):
    """``(x0, y0)``: signed Gaussian rows scaled to unit length (Spark's
    ``ALS.initialize``), float32, from the FIT's seed by NumPy alone."""
    rng = np.random.default_rng([int(seed), 0x1417])

    def rows(n):
        f = rng.standard_normal((n, cfg["rank"]), dtype=np.float32)
        f /= np.maximum(np.linalg.norm(f, axis=1, keepdims=True), 1e-12)
        return f

    return rows(cfg["users"]), rows(cfg["items"])


def width(r):
    return r * (r + 1) // 2 + r + 1


class Table:
    """The three arrays on the device, padded with ratings of score 0 (which
    add nothing) to whole blocks."""

    def __init__(self, x_host, precision="highest"):
        jax, jnp = _jax()
        users, items, ratings = x_host
        self.nnz = len(users)
        self.block = min(BLOCK_EDGES, self.nnz)
        pad = -self.nnz % self.block

        def up(a, dtype):
            return jnp.pad(jnp.asarray(np.asarray(a, dtype=dtype)), (0, pad))

        self.users, self.items = up(users, np.int32), up(items, np.int32)
        self.ratings = stored(up(ratings, np.float32), precision)
        self.blocks = (self.nnz + pad) // self.block


@functools.lru_cache(maxsize=None)
def _moments_program(n_dst, r, block, precision, zero_is_preferred):
    jax, jnp = _jax()
    iu, ju = np.triu_indices(r)

    @jax.jit
    def moments(dst, src, ratings, factors, alpha, blocks):
        f = stored(factors, precision)

        def body(k, sheet):
            lo = k * block
            d, s, c = (jax.lax.dynamic_slice_in_dim(a, lo, block)
                       for a in (dst, src, ratings))
            ys = f[s]  # (block, r)
            c1 = alpha * jnp.abs(c)
            pos = (c >= 0 if zero_is_preferred else c > 0).astype(jnp.float32)
            m = jnp.concatenate(
                [
                    (ys * c1[:, None])[:, iu] * ys[:, ju],
                    ys * ((1.0 + c1) * pos)[:, None],
                    pos[:, None],
                ],
                axis=1,
            )
            return stored(
                sheet + jax.ops.segment_sum(m, d, num_segments=n_dst), precision
            )

        return jax.lax.fori_loop(
            0, blocks, body, jnp.zeros((n_dst, width(r)), jnp.float32)
        )

    return moments


def _triangle_of(r):
    """For each entry of an (r, r) matrix, row-major, where the upper
    triangle (as ``numpy.triu_indices`` lists it) holds its value."""
    where = np.zeros((r, r), np.int64)
    iu, ju = np.triu_indices(r)
    where[iu, ju] = where[ju, iu] = np.arange(len(iu))
    return where.reshape(-1)


def solve_rows(sheet, gram, reg, r):
    """The float64 solves of one half-update from its float32 moment sheet:
    ``(G + A_d + reg n_d I) x_d = b_d`` for every destination with a
    preferred rating, the zero vector for the others.  In row chunks on
    host threads: the casts and copies of a chunk run beside another's
    solves (``numpy.linalg.solve``'s own loop holds the GIL)."""
    n_dst, tri = sheet.shape[0], r * (r + 1) // 2
    unpack, eye = _triangle_of(r), np.eye(r)
    out = np.zeros((n_dst, r))

    def chunk(lo):
        m = sheet[lo:lo + SOLVE_CHUNK].astype(np.float64)
        n = m[:, -1]
        a = np.take(m, unpack, axis=1).reshape(-1, r, r) + gram
        a += (reg * n)[:, None, None] * eye
        live = n > 0
        a[~live] = eye
        x = np.linalg.solve(a, m[:, tri:tri + r, None])[:, :, 0]
        out[lo:lo + SOLVE_CHUNK] = np.where(live[:, None], x, 0.0)

    with ThreadPoolExecutor(SOLVE_THREADS) as pool:
        list(pool.map(chunk, range(0, n_dst, SOLVE_CHUNK)))
    return out


@functools.lru_cache(maxsize=None)
def _device_solve_program(r, precision):
    """The same systems solved on the device in float32: Gaussian
    elimination without pivoting (the matrices are positive definite) and
    back-substitution, written out for the r rows, every step one
    operation over all destinations at once."""
    jax, jnp = _jax()
    iu, ju = np.triu_indices(r)

    @jax.jit
    def solve(sheet, gram, reg):
        tri = sheet[:, : len(iu)].T  # (r(r+1)/2, n)
        n = sheet[:, -1]
        a = [[None] * r for _ in range(r)]
        for k, (i, j) in enumerate(zip(iu, ju)):
            a[i][j] = a[j][i] = tri[k] + gram[i, j] + (reg * n if i == j else 0.0)
        b = [sheet[:, len(iu) + i] for i in range(r)]
        for j in range(r):
            for i in range(j + 1, r):
                f = a[i][j] / a[j][j]
                for k in range(j + 1, r):
                    a[i][k] = a[i][k] - f * a[j][k]
                b[i] = b[i] - f * b[j]
        x = [None] * r
        for i in reversed(range(r)):
            acc = b[i]
            for k in range(i + 1, r):
                acc = acc - a[i][k] * x[k]
            x[i] = acc / a[i][i]
        out = jnp.where(n[None, :] > 0, jnp.stack(x, axis=0), 0.0)
        return stored(out.T, precision)

    return solve


def half_step(table, dst, src, n_dst, factors, cfg, precision="highest",
              solve="host64"):
    """The other side's factors from ``factors``: float32 ``(n_dst, r)``.
    ``solve``: ``"host64"`` (``solve_rows``: what ``judge`` holds a fit's
    last half-update to) or ``"device32"`` (``_device_solve_program``: a
    third of the seconds; what the iterations of ``fit_plain`` and of the
    replay run on)."""
    jax, jnp = _jax()
    r = cfg["rank"]
    f = jnp.asarray(factors)
    sheet = _moments_program(
        n_dst, r, table.block, precision, bool(cfg.get("zero_is_preferred"))
    )(dst, src, table.ratings, f, np.float32(cfg["alpha"]), table.blocks)
    fs = stored(f, precision)
    gram = jnp.matmul(fs.T, fs, precision=matmul(precision))
    if solve == "device32":
        return _device_solve_program(r, precision)(
            sheet, gram, np.float32(cfg["reg_param"]))
    out = solve_rows(np.asarray(sheet), np.asarray(gram, dtype=np.float64),
                     cfg["reg_param"], r)
    return np.asarray(stored(jnp.asarray(out.astype(np.float32)), precision))


def update_users(table, y, cfg, precision="highest", solve="host64"):
    return half_step(table, table.users, table.items, cfg["users"], y, cfg,
                     precision, solve)


def update_items(table, x, cfg, precision="highest", solve="host64"):
    return half_step(table, table.items, table.users, cfg["items"], x, cfg,
                     precision, solve)


def iterate(table, x, y, cfg, precision="highest"):
    """``max_iter`` iterations from ``(x, y)``, the factors staying on the
    device between half-updates; float32 ndarrays back."""
    for _ in range(cfg["max_iter"]):
        x = update_users(table, y, cfg, precision, "device32")
        y = update_items(table, x, cfg, precision, "device32")
    return np.asarray(x, np.float32), np.asarray(y, np.float32)


@functools.lru_cache(maxsize=None)
def _objective_program(block, blocks):
    jax, jnp = _jax()

    @jax.jit
    def observed(users, items, ratings, x, y, alpha):
        def body(k, acc):
            lo = k * block
            u, i, c = (jax.lax.dynamic_slice_in_dim(a, lo, block)
                       for a in (users, items, ratings))
            s = jnp.sum(x[u] * y[i], axis=1)
            conf = 1.0 + alpha * jnp.abs(c)
            pos = (c > 0).astype(jnp.float32)
            # this pair's term of the loss minus what the sum over ALL
            # pairs (the Gram product below) already holds for it
            return acc.at[k].set(jnp.sum(conf * (pos - s) ** 2 - s * s))

        return jax.lax.fori_loop(0, blocks, body, jnp.zeros((blocks,), jnp.float32))

    return observed


def objective(table, x, y, counts, cfg):
    """The implicit loss of ``(x, y)`` over ALL pairs, rated or not:
    sum c (p - x.y)^2 + lambda (sum n_u |x_u|^2 + sum n_i |y_i|^2), the
    unrated pairs' share from the two Grams, in float64 on the host."""
    jax, jnp = _jax()
    per_block = _objective_program(table.block, table.blocks)(
        table.users, table.items, table.ratings, jnp.asarray(x), jnp.asarray(y),
        np.float32(cfg["alpha"]),
    )
    x64, y64 = np.asarray(x, np.float64), np.asarray(y, np.float64)
    every_pair = float(np.sum((x64.T @ x64) * (y64.T @ y64)))
    n_u, n_i = counts
    ridge = cfg["reg_param"] * (
        float(n_u @ np.sum(x64 * x64, axis=1)) + float(n_i @ np.sum(y64 * y64, axis=1))
    )
    return float(np.sum(np.asarray(per_block, np.float64))) + every_pair + ridge


def preferred_counts(x_host, cfg):
    users, items, ratings = x_host
    pos = np.asarray(ratings) > 0
    return (
        np.bincount(np.asarray(users)[pos], minlength=cfg["users"]).astype(np.float64),
        np.bincount(np.asarray(items)[pos], minlength=cfg["items"]).astype(np.float64),
    )


def fit_plain(x_host, cfg, seed, precision="highest"):
    """A whole fit of its own in one precision throughout, from
    ``init_factors(cfg, seed)``.  ``cfg`` may carry a planted fault
    (``control_faults``): ``max_iter`` 0, or ``zero_is_preferred`` (a rating
    of score 0 counted as a preference)."""
    table = Table(x_host, precision)
    x0, y0 = init_factors(cfg, seed)
    x, y = iterate(table, x0, y0, cfg, precision)
    return {"user_factors": np.asarray(x, np.float32),
            "item_factors": np.asarray(y, np.float32), "seed": int(seed)}


def _rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / max(np.linalg.norm(want), 1e-30))


def _well_formed(result, cfg):
    shapes = {"user_factors": (cfg["users"], cfg["rank"]),
              "item_factors": (cfg["items"], cfg["rank"])}
    return all(
        isinstance(result.get(k), np.ndarray) and result[k].shape == shape
        and np.all(np.isfinite(result[k])) for k, shape in shapes.items()
    ) and "seed" in result


def judge(x_host, cfg, results, seed):
    """The numbers that decide ``correct`` for the fits of one window, the
    worst fit's of each:

    - ``half_step_gap``: every fit's returned item factors against the
      reference's ONE item half-update from the returned user factors,
      relative Frobenius: the last thing a fit does, over every rating.
    - ``replay_gap``: the first and the last fit's factors against the
      reference's own ``max_iter`` iterations from the same initial factors,
      relative Frobenius, the worse side.
    - ``objective_gap``: for the same fits, the implicit loss of the
      returned factors against that of the replay's, relative.
    - ``shape_gap``: 1 where an answer is malformed (shape, non-finite).
    """
    worst = {"half_step_gap": 0.0, "replay_gap": 0.0, "objective_gap": 0.0,
             "shape_gap": 0.0}
    table = Table(x_host)
    counts = None
    replayed = {range(len(results))[i] for i in REPLAYED} if results else set()
    for k, res in enumerate(results):
        if not _well_formed(res, cfg):
            worst["shape_gap"] = 1.0
            continue
        x, y = res["user_factors"], res["item_factors"]
        y_ref = update_items(table, x, cfg).astype(np.float64)
        worst["half_step_gap"] = max(worst["half_step_gap"], _rel(y, y_ref))
        if k not in replayed:
            continue
        if counts is None:
            counts = preferred_counts(x_host, cfg)
        x_r, y_r = iterate(table, *init_factors(cfg, res["seed"]), cfg)
        worst["replay_gap"] = max(
            worst["replay_gap"], _rel(x, x_r.astype(np.float64)),
            _rel(y, y_r.astype(np.float64)),
        )
        want = objective(table, x_r, y_r, counts, cfg)
        got = objective(table, x, y, counts, cfg)
        worst["objective_gap"] = max(
            worst["objective_gap"], abs(got - want) / max(abs(want), 1e-30)
        )
    return worst
