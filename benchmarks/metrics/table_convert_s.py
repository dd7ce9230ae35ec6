"""Staging: mean over the window's fits of the ``table_convert`` phase wall of
``summary.timings`` (host copy, padding to the bucket, ``device_put``).  The
phase ends at ``device_put``, which may return before the bytes land, so part
of the upload can be booked to the phase that follows (PERF.md, section 7)."""


def read(ctx):
    return ctx.phase_mean_s("table_convert")
