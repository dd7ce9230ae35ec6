"""Estimator: the fit's wall minus the sum of its phase walls in
``summary.timings`` (the configuration's ``phases``), mean over the window's
fits: ``np.asarray``/``astype`` of the table, the planner, the resilience
ladder, the summary, host fetches outside phases."""


def read(ctx):
    rest = [
        f["wall_s"] - sum(f["info"]["phases"].get(p, 0.0) for p in ctx.cfg["phases"])
        for f in ctx.good_fits if f["info"].get("phases")
    ]
    return sum(rest) / len(rest) if rest else None
