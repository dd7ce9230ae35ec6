"""Implicit ALS through the public entry ``oap_mllib_tpu.ALS(...).fit(users,
items, ratings, n_users=, n_items=, init=)``.

See ``estimators/kmeans.py`` for what an adapter gives the harness.  For
this estimator a ROW is a rating: the harness's ``rows`` (``rows_per_chip``
x chips) is the length of the three arrays ``make_data`` returns, and the
table ``x`` every other part is handed is that tuple ``(users int32, items
int32, ratings float32)``, as a Spark executor holds one user block.

The initial factors are made from the fit's seed with NumPy alone
(``reference/als_implicit_ref.init_factors``, the one definition) and handed
over through ``init=``: the plain reference starts from the same ones
without importing anything of the program.  A fit whose
``table_convert/upload`` span shows a piece larger than the configuration's ``expect_upload.piece_bytes_max`` (or no such span:
the layouts went up whole) cannot run the configuration; the run says so at
that fit and ends with exit code ``EXIT_CANNOT_STAGE``
(``estimators/pca_staged.py``).
"""

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference.als_implicit_ref import init_factors  # noqa: F401  (one definition)

REFERENCE = "als_implicit_ref"
EXIT_CANNOT_STAGE = 4
UPLOAD_SPAN = "table_convert/upload"
GROUP_SPAN = "table_convert/group_edges"
ITER_PHASE = "als_iterations"
GEN_BLOCK_USERS = 4096  # about a million ratings a block
GEN_THREADS = max(1, min(12, len(os.sched_getaffinity(0))))
SCORE_SLOTS = 4096  # the score law, quantised to 1/4096


def score_table(law):
    """The discrete law of the scores as ``SCORE_SLOTS`` equally likely
    slots: ``zero_share`` on 0, ``tens_share`` spread over 10, 20 ... 100
    by ``tens_weights``, the rest uniform on the 90 other whole numbers of
    1 ... 99.  Shares are rounded to whole slots, largest remainder first."""
    pmf = np.zeros(101)
    pmf[0] = law["zero_share"]
    tens = np.asarray(law["tens_weights"], dtype=np.float64)
    pmf[10::10] = law["tens_share"] * tens / tens.sum()
    others = [s for s in range(1, 100) if s % 10]
    pmf[others] = (1.0 - law["zero_share"] - law["tens_share"]) / len(others)
    exact = pmf * SCORE_SLOTS
    slots = np.floor(exact).astype(np.int64)
    short = SCORE_SLOTS - int(slots.sum())
    slots[np.argsort(-(exact - slots), kind="stable")[:short]] += 1
    return np.repeat(np.arange(101, dtype=np.float32), slots)


def user_degrees(cfg, rows, n_users, rng):
    """Ratings a user: ``degree_min`` + a lognormal share of the rest, so
    that the degrees sum to exactly ``rows`` (largest remainders get the
    odd ratings)."""
    law = cfg["data"]
    lo = law["degree_min"]
    w = rng.lognormal(0.0, law["degree_sigma"], n_users)
    exact = (rows - lo * n_users) * w / w.sum()
    deg = np.floor(exact).astype(np.int64)
    short = rows - lo * n_users - int(deg.sum())
    deg[np.argsort(-(exact - deg), kind="stable")[:short]] += 1
    return deg + lo


def make_data(cfg, rows, seed):
    """``(users, items, ratings)``: int32, int32, float32 arrays of length
    ``rows``, on the host, in the order the data set's files hold them:
    each user's ratings together, users ascending, a user's items in no
    order.  Users draw their degree (``user_degrees``), each rating its
    item from a Zipf-like law over a seeded permutation of the items
    (rank ``floor(z) - offset`` of a continuous power-law ``z`` on
    [offset, items + offset) with exponent ``item_exponent``) and its
    score from ``score_table``; a pair may repeat.  Drawn in blocks of
    users from seeds of their own, so that threads can share the work and
    the arrays do not depend on their number."""
    law = cfg["data"]
    n_users, n_items = cfg["users"], cfg["items"]
    root = np.random.SeedSequence([int(seed), 0xA15DA7A])
    n_blocks = -(-n_users // GEN_BLOCK_USERS)
    seeds = root.spawn(n_blocks + 1)
    head = np.random.default_rng(seeds[0])
    deg = user_degrees(cfg, rows, n_users, head)
    item_of_rank = head.permutation(n_items).astype(np.int32)
    scores = score_table(law["scores"])
    starts = np.concatenate([[0], np.cumsum(deg)])
    s, q = law["item_exponent"], law["item_offset"]
    a, b = q ** (1.0 - s), (n_items + q) ** (1.0 - s)
    users = np.empty(rows, np.int32)
    items = np.empty(rows, np.int32)
    ratings = np.empty(rows, np.float32)

    def fill(i):
        rng = np.random.default_rng(seeds[i + 1])
        u0, u1 = i * GEN_BLOCK_USERS, min((i + 1) * GEN_BLOCK_USERS, n_users)
        lo, hi = int(starts[u0]), int(starts[u1])
        users[lo:hi] = np.repeat(np.arange(u0, u1, dtype=np.int32), deg[u0:u1])
        z = rng.random(hi - lo)
        z *= b - a
        z += a
        np.power(z, 1.0 / (1.0 - s), out=z)
        rank = np.minimum((z - q).astype(np.int64), n_items - 1)
        items[lo:hi] = item_of_rank[rank]
        ratings[lo:hi] = scores[rng.integers(SCORE_SLOTS, size=hi - lo)]

    with ThreadPoolExecutor(GEN_THREADS) as pool:
        list(pool.map(fill, range(n_blocks)))
    return users, items, ratings


def program_settings(cfg):
    return dict(
        cfg["program_config"], matmul_precision=cfg["matmul_precision"],
        compute_precision=cfg["compute_precision"],
        als_precision=cfg["als_precision"],
    )


def upload_breach(cfg, attrs):
    """Why an upload with these span attributes (None: the fit recorded no
    such span) breaks the configuration's ``expect_upload``, or None."""
    limit = cfg.get("expect_upload", {}).get("piece_bytes_max")
    if limit is None:
        return None
    if attrs is None or "bytes" not in attrs:
        return (f"the fit recorded no {UPLOAD_SPAN} span: its layouts went up "
                f"whole; the configuration allows {limit} bytes a piece")
    piece_bytes = -(-attrs["bytes"] // max(attrs.get("pieces", 1), 1))
    if piece_bytes <= limit:
        return None
    return (f"{UPLOAD_SPAN} sent {attrs['bytes']} bytes in "
            f"{attrs.get('pieces', 1)} piece(s) of {piece_bytes}; the "
            f"configuration allows {limit} a piece")


def _attrs(timings, phases, path):
    return dict(timings.root.node(path).attrs) if path in phases else None


def fit(cfg, x, seed):
    """One whole fit; returns (the factors and the seed they started from,
    what the summary says).  The fit ends when both factor tables are on
    the host."""
    from oap_mllib_tpu import ALS

    users, items, ratings = x
    model = ALS(
        rank=cfg["rank"], max_iter=cfg["max_iter"], reg_param=cfg["reg_param"],
        implicit_prefs=cfg["implicit_prefs"], alpha=cfg["alpha"], seed=seed,
        # this chip holds ONE of the deployment's user blocks (Spark's
        # numUserBlocks, of which the process sees its own)
        num_user_blocks=1,
    ).fit(users, items, ratings, n_users=cfg["users"], n_items=cfg["items"],
          init=init_factors(cfg, seed))
    s = model.summary
    timings = s["timings"]
    phases = dict(timings.as_dict())
    upload = _attrs(timings, phases, UPLOAD_SPAN)
    breach = upload_breach(cfg, upload)
    if breach:
        print(f"estimators/als_implicit.py: this program cannot run "
              f"{cfg['name']}: {breach}", file=sys.stderr, flush=True)
        raise SystemExit(EXIT_CANNOT_STAGE)
    result = {
        "user_factors": np.array(model.user_factors_),
        "item_factors": np.array(model.item_factors_),
        "seed": int(seed),
    }
    info = {
        "phases": phases,
        "kernel": s.get("als_kernel"),
        "accelerated": bool(s.get("accelerated", False)),
        "resilience": dict(s.get("resilience") or {}),
        "iterations": int(cfg["max_iter"]),
        "staging": dict(upload or {}, **(_attrs(timings, phases, GROUP_SPAN) or {})),
    }
    return result, info


# -- the work a fit requires ---------------------------------------------------
# One half-update of n_dst destinations from N ratings at rank r (Hu, Koren,
# Volinsky 2008, with the Gram trick):
#   per rating        r(r+1) operations: the upper triangle of c1 * y y^T
#                     (r(r+1)/2 multiply-adds) -- and 2r for the right-hand
#                     side, counted with it below --, and one read of
#                     12 + 4r bytes: the (id, id, score) triple and the
#                     source factor row it gathers;
#   per destination   one r^3/3 Cholesky solve (substitutions are O(r^2));
#                     the factor row written, 4r bytes;
#   the Gram          2 n_src r^2 operations, n_src rows of 4r bytes read.
# Whatever implements them (grouped matmuls over padded slots, scatters, a
# fused solve) is held to this; pad slots are no required work.


def half_update_work(ratings, n_dst, n_src, r):
    return {
        "flops": ratings * (r * (r + 1.0) + 2.0 * r) + n_dst * r ** 3 / 3.0
        + 2.0 * n_src * r * r,
        "bytes": ratings * (12.0 + 4.0 * r) + 4.0 * r * (n_dst + n_src),
    }


def _add(*works):
    return {k: sum(w[k] for w in works) for k in ("flops", "bytes")}


def _times(work, m):
    return {k: work[k] * m for k in ("flops", "bytes")}


def phase_work(cfg, rows, info):
    """Required work by phase for one fit of ``info['iterations']``
    iterations: a user half-update and an item half-update each."""
    r, nu, ni = cfg["rank"], cfg["users"], cfg["items"]
    one = _add(half_update_work(rows, nu, ni, r), half_update_work(rows, ni, nu, r))
    return {ITER_PHASE: _times(one, info.get("iterations", cfg["max_iter"]))}


def fit_work(cfg, rows, info):
    return _add(*phase_work(cfg, rows, info).values())
