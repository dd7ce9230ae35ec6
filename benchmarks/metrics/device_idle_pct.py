"""Device: 100 * (1 - union of the device operations' intervals over the
traced window), on the busiest device.  Nothing without a device trace."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s(tr.busiest()) / tr.window_s)
