"""Kernels: the least time the chip could take for the solves of the
window's half-updates (``estimators/als_implicit_r100.solve_work``: per
destination an r^3/3 Cholesky and two r^2 substitutions, and one read of
its r^2 + 2r floats) over the device time of the ``als_block_cholesky``
kernel on the busiest device.  Required work, never issued work: the share
cannot pass 100%.  Nothing without a device trace or the kernel in it."""

from metrics.als_moments_roofline import kernel_roofline

KERNEL = "als_block_cholesky"


def read(ctx):
    return kernel_roofline(ctx, KERNEL, "solve_work")
