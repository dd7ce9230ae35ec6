"""Staging: mean ``table_convert/upload`` sub-span wall, from ``device_put`` of
table and mask until ``block_until_ready`` on both returns: the bytes have
landed.  The span's ``attrs["bytes"]`` over this wall is the host-to-device
rate."""


def read(ctx):
    return ctx.phase_mean_s("table_convert/upload")
