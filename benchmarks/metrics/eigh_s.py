"""Kernels: mean ``eigh`` phase wall (the eigensolve and the fetch of the
spectrum; the device work of ``covariance`` may finish under it)."""


def read(ctx):
    return ctx.phase_mean_s("eigh")
