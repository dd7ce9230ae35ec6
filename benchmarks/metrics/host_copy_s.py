"""Staging: mean ``table_convert/host_copy`` sub-span wall (the dtype copy of
the host table, padding to the row bucket, the mask; the device is idle)."""


def read(ctx):
    return ctx.phase_mean_s("table_convert/host_copy")
