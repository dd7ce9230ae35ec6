"""Kernels: mean ``init_centers`` phase wall (k-means|| rounds on the device
and the k-means++ reduction of the candidates on the host)."""


def read(ctx):
    return ctx.phase_mean_s("init_centers")
