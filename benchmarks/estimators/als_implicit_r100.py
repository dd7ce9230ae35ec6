"""Implicit ALS at rank 100 through the public entry, as
``estimators/als_implicit.py`` drives rank 10: the same table
(``make_data``), the same initial factors handed over through ``init=``,
the same required work (``phase_work``, ``fit_work``, which take
``cfg["rank"]``), the same staging bound.  What differs:

- the plain reference is ``reference/als_implicit_r100_ref.py``: at rank
  100 the rank-10 reference's per-rating sheet would be 5,151 floats a
  rating;
- a fit also reports the ``als_iterations`` span's attributes (the route,
  its block of destinations and the blocks of each side) under
  ``info["iterations_span"]``, which the ``als_dst_blocks`` reader reads;
- a program that cannot run the configuration says so at its FIRST fit
  and ends the run with ``EXIT_CANNOT_RUN``: a fit that raises, or one
  that ran another kernel than ``expect_kernel`` (a program without the
  destination-blocked route cannot hold rank 100's moments on the chip,
  and its fallbacks would take hours);
- ``moments_work`` and ``solve_work``: the required work of the two
  kernels of the route, which the rooflines ``als_moments_roofline`` and
  ``als_solve_roofline`` divide by the kernels' device time.
"""

import sys

import numpy as np

from estimators import als_implicit as base
from estimators.als_implicit import (  # noqa: F401  (the rank-10 adapter's)
    ITER_PHASE,
    fit_work,
    make_data,
    phase_work,
    program_settings,
)

REFERENCE = "als_implicit_r100_ref"
EXIT_CANNOT_RUN = 5
EXIT_CANNOT_STAGE = base.EXIT_CANNOT_STAGE


def _cannot_run(cfg, why):
    print(f"estimators/als_implicit_r100.py: this program cannot run "
          f"{cfg['name']}: {why}", file=sys.stderr, flush=True)
    raise SystemExit(EXIT_CANNOT_RUN)


def fit(cfg, x, seed):
    """One whole fit, as ``als_implicit.fit`` makes it; the fit ends when
    both factor tables are on the host."""
    from oap_mllib_tpu import ALS

    users, items, ratings = x
    try:
        model = ALS(
            rank=cfg["rank"], max_iter=cfg["max_iter"],
            reg_param=cfg["reg_param"], implicit_prefs=cfg["implicit_prefs"],
            alpha=cfg["alpha"], seed=seed, num_user_blocks=1,
        ).fit(users, items, ratings, n_users=cfg["users"],
              n_items=cfg["items"], init=base.init_factors(cfg, seed))
    except Exception as e:  # noqa: BLE001 — any failure ends the run
        _cannot_run(cfg, f"the fit raised {type(e).__name__}: {str(e)[:400]}")
    s = model.summary
    if s.get("als_kernel") != cfg["expect_kernel"]:
        _cannot_run(cfg, f"the fit ran kernel {s.get('als_kernel')!r}, the "
                         f"configuration needs {cfg['expect_kernel']!r}")
    timings = s["timings"]
    phases = dict(timings.as_dict())
    upload = base._attrs(timings, phases, base.UPLOAD_SPAN)
    breach = base.upload_breach(cfg, upload)
    if breach:
        print(f"estimators/als_implicit_r100.py: this program cannot run "
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
        "staging": dict(upload or {},
                        **(base._attrs(timings, phases, base.GROUP_SPAN) or {})),
        "iterations_span": base._attrs(timings, phases, ITER_PHASE) or {},
    }
    return result, info


# -- the required work of the route's two kernels ------------------------------
# Per rating of a half-update (the moments): r(r+1) operations for the upper
# triangle of c1 * y y^T and 2r for the right-hand side, and one read of
# 12 + 4r bytes, the (id, id, score) triple and the source row.  Per
# destination (the solve): an r^3/3 Cholesky and two r^2 substitutions, and
# one read of its r^2 + 2r floats (system, right-hand side and the factor
# row written).  Pad slots and the lanes a tile pads to are no required work.


def moments_work(cfg, rows, info):
    r = cfg["rank"]
    n = 2.0 * rows * info.get("iterations", cfg["max_iter"])
    return {"flops": n * (r * (r + 1.0) + 2.0 * r), "bytes": n * (12.0 + 4.0 * r)}


def solve_work(cfg, rows, info):
    r = cfg["rank"]
    n = (cfg["users"] + cfg["items"]) * float(info.get("iterations", cfg["max_iter"]))
    return {"flops": n * (r ** 3 / 3.0 + 2.0 * r * r),
            "bytes": n * 4.0 * (r * r + 2.0 * r)}
