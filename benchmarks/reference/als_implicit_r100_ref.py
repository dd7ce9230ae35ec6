"""Plain implicit-feedback ALS (Hu, Koren, Volinsky 2008) at any rank, with
Spark ML's conventions as ``reference/als_implicit_ref.py`` states them
(confidence ``1 + alpha * |r|``, preference 1 only for ``r > 0``, lambda
scaled by each row's count of ``r > 0`` ratings, zeros for a row without
one), for ranks at which that reference's per-rating sheet of
``r(r+1)/2 + r + 1`` floats cannot be held: 5,151 floats a rating at rank
100.

Imports nothing of the program and takes nothing the program made.

- **Layout** (:class:`Side`, built with NumPy in set-up): a side's ratings
  sorted by destination and each destination's ratings padded to a dense
  row of ``K`` slots, ``K`` the least power of two (at least
  ``LEAST_ROW``) that holds them; the destinations of one ``K`` are
  stacked, ``BLOCK_SLOTS // K`` rows a block.  A pad slot names source
  ``n_src`` and reads a zero row.
- **Moments**: a block's source rows gathered on the device and
  contracted with ``jnp.einsum`` at the precision asked for:
  ``A = Y^T diag(alpha |r|) Y``, ``b = Y^T ((1 + alpha |r|) [r > 0])``
  and the count, one block of destinations at a time.
- **Solves**: the iterations (``iterate``: ``fit_plain`` and the replay)
  solve ``(G + A + reg n I) x = b`` on the device in float32,
  ``SOLVE_ROWS`` destinations at a time, by a Cholesky factorization and
  two substitutions written out with the batch on the lanes (XLA's
  ``jnp.linalg.cholesky`` took 274 ms for 16,384 systems of rank 100 on
  a v5e, which would make the replay alone last minutes);
  ``judge``'s half-step solves a seeded sample of ``SAMPLE_ITEMS`` item
  rows, always with the ``TOP_ITEMS`` most rated, with float64
  ``numpy.linalg.solve`` on the host.

``init_factors`` and ``objective`` are the rank-10 reference's (the
adapter hands the program the same initial factors).  Precisions: see
``precision.py``; under ``bfloat16`` the scores, the factors gathered, the
moments and the factors returned are stored in bfloat16.
"""

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference.als_implicit_ref import (  # noqa: F401  (one definition each)
    REPLAYED,
    Table,
    _rel,
    _well_formed,
    init_factors,
    objective,
    preferred_counts,
)
from reference.precision import matmul, stored

BLOCK_SLOTS = 1 << 20  # slots a block's gather holds (400 MB of rows at rank 100)
SOLVE_ROWS = 8192  # destinations a device solve takes
SEGMENT = 10  # columns of the factorization between two shrinks
LEAST_ROW = 32
SAMPLE_ITEMS = 65536
TOP_ITEMS = 64
SOLVE_CHUNK = 1024  # sampled rows a host thread solves at a time
SOLVE_THREADS = max(1, min(12, len(os.sched_getaffinity(0))))


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def sort_by_destination(dst, src, ratings, n_dst):
    """``(deg, first, src, score)``: each destination's rating count and
    first position, the sources and scores in destination order.  The
    order is a stable sort by the low 16 bits and then by the high ones,
    each a radix sort in NumPy (a stable sort of 32-bit keys is a merge
    sort: seconds longer at 126M ratings)."""
    dst = np.asarray(dst)
    order = np.argsort((dst & 0xFFFF).astype(np.uint16), kind="stable")
    order = order[np.argsort((dst[order] >> 16).astype(np.uint16), kind="stable")]
    deg = np.bincount(dst, minlength=n_dst)
    first = np.zeros(n_dst + 1, np.int64)
    np.cumsum(deg, out=first[1:])
    return (deg, first, np.asarray(src, np.int32)[order],
            np.asarray(ratings, np.float32)[order])


class Side:
    """One update direction's dense layout on the device, from
    :func:`sort_by_destination`: for each row width ``K``, ``(blocks,
    rows, K)`` source ids and scores and the ``(blocks, rows)``
    destination each row solves (``n_dst`` on pad rows, which are
    dropped); ``keep`` limits it to some destinations."""

    def __init__(self, by_dst, n_dst, n_src, keep=None):
        _, jnp = _jax()
        deg, first, src_sorted, score_sorted = by_dst
        width = np.maximum(LEAST_ROW, 1 << np.ceil(
            np.log2(np.maximum(deg, 1))).astype(np.int64))
        rated = deg > 0 if keep is None else (deg > 0) & keep
        self.n_dst, self.n_src = n_dst, n_src
        self.classes = []
        for k in np.unique(width[rated]):
            ids = np.flatnonzero(rated & (width == k)).astype(np.int32)
            rows = max(1, min(SOLVE_ROWS, BLOCK_SLOTS // int(k)))
            blocks = -(-len(ids) // rows)
            s = np.full((blocks * rows, k), n_src, np.int32)
            c = np.zeros((blocks * rows, k), np.float32)
            d = np.full(blocks * rows, n_dst, np.int32)
            d[:len(ids)] = ids
            # slot j of row i: the j-th rating of destination ids[i]
            counts = deg[ids]
            row = np.repeat(np.arange(len(ids)), counts)
            col = np.arange(len(row)) - np.repeat(
                np.cumsum(counts) - counts, counts)
            at = np.repeat(first[ids], counts) + col
            s[row, col] = src_sorted[at]
            c[row, col] = score_sorted[at]
            self.classes.append((
                jnp.asarray(s.reshape(blocks, rows, k)),
                jnp.asarray(c.reshape(blocks, rows, k)),
                jnp.asarray(d.reshape(blocks, rows)),
            ))


@functools.lru_cache(maxsize=None)
def _moments_program(precision, zero_is_preferred):
    jax, jnp = _jax()

    @jax.jit
    def moments(factors, src, score, alpha, n_src):
        """``(A, b, n)`` of one block's rows: ``(rows, r, r)``, ``(rows,
        r)``, ``(rows,)``; ``factors`` hold ``n_src`` rows and zeros
        below (both sides' blocks share a program)."""
        f, score = stored(factors, precision), stored(score, precision)
        y = jnp.take(f, src, axis=0, mode="fill", fill_value=0.0)
        rated = (src < n_src).astype(jnp.float32)
        c1 = alpha * jnp.abs(score) * rated
        pos = (score >= 0 if zero_is_preferred else score > 0).astype(
            jnp.float32) * rated
        a = jnp.einsum("nkr,nks->nrs", y * c1[..., None], y,
                       precision=matmul(precision))
        b = jnp.einsum("nk,nkr->nr", (1.0 + c1) * pos, y,
                       precision=matmul(precision))
        return stored(a, precision), stored(b, precision), pos.sum(axis=1)

    return moments


def _cholesky_solve(a, b):
    """Float32 solutions of the ``(B, r, r)`` positive definite systems
    ``a x = b``, with the batch on the lanes: the Cholesky factor of
    ``[[a, b], [b^T, 0]]`` a column at a time by rank-1 downdates of the
    trailing matrix, whose last row is then ``z`` of ``L z = b``, and the
    back substitution ``L^T x = z``.  The trailing matrix shrinks every
    ``SEGMENT`` columns (a loop over a segment's columns, masks inside
    it): a few loops to compile, where one step a column would be
    hundreds of operations."""
    jax, jnp = _jax()
    lax = jax.lax
    n, r = b.shape
    m = jnp.concatenate([jnp.transpose(a, (1, 2, 0)), b.T[None]], axis=0)
    m = jnp.concatenate([m, jnp.concatenate(
        [b.T, jnp.zeros((1, n), jnp.float32)])[:, None, :]], axis=1)
    columns = []  # per segment: (first column, (S, rows from it, B))
    for j0 in range(0, r, SEGMENT):
        seg = min(SEGMENT, r - j0)
        size = r + 1 - j0
        idx = jnp.arange(size)

        def step(t, carry, size=size, idx=idx):
            trail, cols = carry
            col = lax.dynamic_index_in_dim(trail, t, axis=1, keepdims=False)
            col = jnp.where((idx >= t)[:, None], col / jnp.sqrt(col[t]), 0.0)
            live = (idx > t)[:, None] & (idx > t)[None, :]
            trail = trail - jnp.where(live[:, :, None],
                                      col[:, None, :] * col[None, :, :], 0.0)
            return trail, lax.dynamic_update_index_in_dim(cols, col, t, 0)

        m, cols = lax.fori_loop(0, seg, step, (
            m, jnp.zeros((seg, size, n), jnp.float32)))
        columns.append((j0, cols))
        m = m[seg:, seg:]
    z = jnp.concatenate([cols[:, -1] for _, cols in columns])  # (r, B)
    x = jnp.zeros((r, n), jnp.float32)
    for j0, cols in reversed(columns):
        def back(i, x, j0=j0, cols=cols):
            t = cols.shape[0] - 1 - i
            col = lax.dynamic_index_in_dim(cols, t, axis=0, keepdims=False)[:-1]
            below = jnp.sum(col * x[j0:], axis=0) - col[t] * x[j0 + t]
            xj = (z[j0 + t] - below) / col[t]
            return lax.dynamic_update_index_in_dim(x, xj, j0 + t, 0)

        x = lax.fori_loop(0, cols.shape[0], back, x)
    return x.T


@functools.lru_cache(maxsize=None)
def _solve_program(precision):
    jax, jnp = _jax()

    @jax.jit
    def solve(a, b, n, gram, reg):
        """The float32 solutions of ``(G + A + reg n I) x = b``, zeros
        where ``n`` is 0: one program for both sides."""
        r = b.shape[1]
        a = a + gram[None] + (reg * n)[:, None, None] * jnp.eye(r)
        x = jnp.where(n[:, None] > 0, _cholesky_solve(a, b), 0.0)
        return stored(x, precision)

    return solve


@functools.lru_cache(maxsize=None)
def _scatter_program():
    jax, _ = _jax()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(out, dst, x):
        return out.at[dst].set(x, mode="drop")

    return scatter


def _rows(factors, cfg):
    """The factors padded with zero rows to the larger side's count."""
    _, jnp = _jax()
    f = jnp.asarray(factors)
    return jnp.pad(f, ((0, max(cfg["users"], cfg["items"]) - f.shape[0]), (0, 0)))


def _gram(factors, precision):
    _, jnp = _jax()
    f = stored(factors, precision)
    return jnp.matmul(f.T, f, precision=matmul(precision))


def half_step(side, factors, cfg, precision="highest"):
    """The other side's float32 ``(n_dst, r)`` factors from ``factors``,
    on the device: the moments a block of rows at a time, the solves
    ``SOLVE_ROWS`` destinations at a time (one program for every width)."""
    _, jnp = _jax()
    f = jnp.asarray(factors)
    r = f.shape[1]
    gram = _gram(f, precision)
    f, n_src = _rows(f, cfg), np.int32(side.n_src)
    moments = _moments_program(precision, bool(cfg.get("zero_is_preferred")))
    solve = _solve_program(precision)
    out = jnp.zeros((side.n_dst, r), jnp.float32)
    alpha, reg = np.float32(cfg["alpha"]), np.float32(cfg["reg_param"])
    for src, score, dst in side.classes:
        per = max(1, SOLVE_ROWS // src.shape[1])  # moment blocks a solve
        for lo in range(0, src.shape[0], per):
            parts = [moments(f, src[k], score[k], alpha, n_src)
                     for k in range(lo, min(lo + per, src.shape[0]))]
            a, b, n = (jnp.concatenate(p) for p in zip(*parts))
            d = jnp.concatenate([dst[k] for k in range(lo, lo + len(parts))])
            pad = SOLVE_ROWS - a.shape[0]
            if pad > 0:
                a = jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                b, n = jnp.pad(b, ((0, pad), (0, 0))), jnp.pad(n, (0, pad))
                d = jnp.pad(d, (0, pad), constant_values=side.n_dst)
            out = _scatter_program()(out, d, solve(a, b, n, gram, reg))
    return out


def sampled_half_step(side, factors, cfg):
    """``(ids, x)``: float64 solutions of the rows ``side`` keeps (the
    sample), moments on the device at ``highest``, solves on the host."""
    _, jnp = _jax()
    f = jnp.asarray(factors)
    gram = np.asarray(_gram(f, "highest"), np.float64)
    f, n_src = _rows(f, cfg), np.int32(side.n_src)
    moments = _moments_program("highest", bool(cfg.get("zero_is_preferred")))
    alpha, reg = np.float32(cfg["alpha"]), cfg["reg_param"]
    parts = []
    for src, score, dst in side.classes:
        for k in range(src.shape[0]):
            a, b, n = moments(f, src[k], score[k], alpha, n_src)
            d = np.asarray(dst[k])
            live = d < side.n_dst
            parts.append((d[live], np.asarray(a)[live], np.asarray(b)[live],
                          np.asarray(n)[live]))
    ids = np.concatenate([p[0] for p in parts])
    a = np.concatenate([p[1] for p in parts])
    b = np.concatenate([p[2] for p in parts])
    n = np.concatenate([p[3] for p in parts])
    r = b.shape[1]
    x = np.zeros((len(ids), r))

    def chunk(lo):
        hi = lo + SOLVE_CHUNK
        m = a[lo:hi].astype(np.float64) + gram
        m += (reg * n[lo:hi].astype(np.float64))[:, None, None] * np.eye(r)
        live = n[lo:hi] > 0
        m[~live] = np.eye(r)
        sol = np.linalg.solve(m, b[lo:hi].astype(np.float64)[:, :, None])[:, :, 0]
        x[lo:hi] = np.where(live[:, None], sol, 0.0)

    with ThreadPoolExecutor(SOLVE_THREADS) as pool:
        list(pool.map(chunk, range(0, len(ids), SOLVE_CHUNK)))
    return ids, x


# The layouts of the last table judged, its item sample and the replays
# made on it: ``control.py`` judges one table many times.
_LAST = {}


def _layouts(x_host, cfg):
    """``(users, items)``: both sides' :class:`Side` of ``x_host``, built
    once a table."""
    if _LAST.get("x") is not x_host:
        _LAST.clear()
        users, items, ratings = x_host
        nu, ni = cfg["users"], cfg["items"]
        by_item = sort_by_destination(items, users, ratings, ni)
        _LAST.update(
            x=x_host, samples={}, replays={}, by_item=by_item,
            sides=(Side(sort_by_destination(users, items, ratings, nu), nu, ni),
                   Side(by_item, ni, nu)),
        )
    return _LAST["sides"]


def _sample(x_host, cfg, seed):
    """The item side's :class:`Side` of the rows the half-step gap reads:
    the ``TOP_ITEMS`` most rated items and a sample drawn from ``seed``,
    ``SAMPLE_ITEMS`` in all."""
    _layouts(x_host, cfg)
    if seed not in _LAST["samples"]:
        ni = cfg["items"]
        deg = _LAST["by_item"][0]
        keep = np.zeros(ni, bool)
        keep[np.argsort(-deg, kind="stable")[:TOP_ITEMS]] = True
        rng = np.random.default_rng([int(seed), 0x5A3])
        rest = np.flatnonzero(~keep & (deg > 0))
        keep[rng.choice(rest, min(len(rest), SAMPLE_ITEMS - TOP_ITEMS),
                        replace=False)] = True
        _LAST["samples"][seed] = Side(_LAST["by_item"], ni, cfg["users"],
                                      keep=keep)
    return _LAST["samples"][seed]


def iterate(x_host, cfg, seed, precision="highest"):
    """``max_iter`` iterations from ``init_factors(cfg, seed)``; float32
    ndarrays back, made once a table, seed, precision and setting."""
    users, items = _layouts(x_host, cfg)
    key = (int(seed), precision, bool(cfg.get("zero_is_preferred")),
           cfg["max_iter"], cfg["rank"], cfg["alpha"], cfg["reg_param"])
    if key not in _LAST["replays"]:
        x, y = init_factors(cfg, seed)
        for _ in range(cfg["max_iter"]):
            x = half_step(users, y, cfg, precision)
            y = half_step(items, x, cfg, precision)
        _LAST["replays"][key] = (np.asarray(x, np.float32),
                                 np.asarray(y, np.float32))
    return _LAST["replays"][key]


def fit_plain(x_host, cfg, seed, precision="highest"):
    """A whole fit of its own in one precision throughout, from
    ``init_factors(cfg, seed)``.  ``cfg`` may carry a planted fault
    (``control_faults``): ``max_iter`` 0, or ``zero_is_preferred``."""
    x, y = iterate(x_host, cfg, seed, precision)
    return {"user_factors": x.copy(), "item_factors": y.copy(), "seed": int(seed)}


def judge(x_host, cfg, results, seed):
    """The numbers that decide ``correct`` for the fits of one window, the
    worst fit's of each:

    - ``half_step_gap``: every fit's returned item factors against the
      reference's item half-update from the returned user factors, on the
      sampled rows, relative Frobenius: the last thing a fit does.
    - ``replay_gap``: the first and the last fit's factors against the
      reference's own ``max_iter`` iterations from the same initial
      factors, relative Frobenius, the worse side.
    - ``objective_gap``: for the same fits, the implicit loss of the
      returned factors against that of the replay's, relative.
    - ``shape_gap``: 1 where an answer is malformed (shape, non-finite).
    """
    worst = {"half_step_gap": 0.0, "replay_gap": 0.0, "objective_gap": 0.0,
             "shape_gap": 0.0}
    coo, counts = None, None
    replayed = {range(len(results))[i] for i in REPLAYED} if results else set()
    for k, res in enumerate(results):
        if not _well_formed(res, cfg):
            worst["shape_gap"] = 1.0
            continue
        x, y = res["user_factors"], res["item_factors"]
        ids, y_ref = sampled_half_step(_sample(x_host, cfg, seed), x, cfg)
        worst["half_step_gap"] = max(worst["half_step_gap"], _rel(y[ids], y_ref))
        if k not in replayed:
            continue
        if coo is None:
            coo, counts = Table(x_host), preferred_counts(x_host, cfg)
        x_r, y_r = iterate(x_host, cfg, res["seed"])
        worst["replay_gap"] = max(
            worst["replay_gap"], _rel(x, x_r.astype(np.float64)),
            _rel(y, y_r.astype(np.float64)),
        )
        want = objective(coo, x_r, y_r, counts, cfg)
        got = objective(coo, x, y, counts, cfg)
        worst["objective_gap"] = max(
            worst["objective_gap"], abs(got - want) / max(abs(want), 1e-30)
        )
    return worst
