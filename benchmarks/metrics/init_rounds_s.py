"""Kernels: mean ``init_centers/rounds`` sub-span wall (the part of the
k-means|| init that waits on the device: the seed-row fetch, every sampling
round with its fetches, the candidates' weights)."""


def read(ctx):
    return ctx.phase_mean_s("init_centers/rounds")
