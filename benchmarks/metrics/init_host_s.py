"""Kernels: mean ``init_centers/kmeanspp_host`` sub-span wall (the host's
weighted k-means++ over the candidates the rounds drew; the device is idle)."""


def read(ctx):
    return ctx.phase_mean_s("init_centers/kmeanspp_host")
