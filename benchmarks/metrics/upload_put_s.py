"""Staging: mean over the window's fits of the ``table_convert/upload/put``
leaf: the host seconds spent INSIDE the upload's ``jax.device_put`` calls
(the transfer's host-side preparation, as far as it is synchronous; the
calls return before the bytes land).  ``count`` on the span is the calls,
``attrs["bytes"]`` what they were handed.  With ``upload_land_s``, the cast
wait and the span's self time it adds up to ``upload_s``.  Nothing where no
fit recorded the leaf (a program from before PR 35)."""


def read(ctx):
    return ctx.phase_mean_s("table_convert/upload/put")
