"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest device,
read when the window closes and before the reference runs, in GB (1e9)."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
