"""Whole fit: the floating-point operations the window's fits REQUIRE (from
shapes and ``num_iter``, ``estimators/<estimator>.fit_work``) over window
seconds x chips x the chip's peak (``peaks.json``, bf16).  Both fits run
float32 at ``highest`` (six bf16 passes a product), so against this peak the
share cannot pass about a sixth today."""


def read(ctx):
    if ctx.peaks is None or not ctx.good_fits:
        return None
    flops = sum(
        ctx.adapter.fit_work(ctx.cfg, ctx.rows, f["info"])["flops"]
        for f in ctx.good_fits
    )
    denom = ctx.run["elapsed_s"] * ctx.cell["chips"] * ctx.peaks["flops_per_s"]
    return 100.0 * flops / denom
