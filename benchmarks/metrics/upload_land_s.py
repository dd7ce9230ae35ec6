"""Staging: mean over the window's fits of the ``table_convert/upload/land``
leaf: the host seconds BLOCKED in the upload's ``jax.block_until_ready``
calls, until bytes put earlier had landed or an in-place write of them had
finished.  Seconds in which the host thread does nothing but wait: what
compute dispatched under the upload could use.  Nothing where no fit
recorded the leaf (a program from before PR 35)."""


def read(ctx):
    return ctx.phase_mean_s("table_convert/upload/land")
