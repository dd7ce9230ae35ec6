"""Kernels: the least time the chip could take for the moments of the
window's half-updates (``estimators/als_implicit_r100.moments_work``: per
rating r(r+1) + 2r operations and one read of 12 + 4r bytes; the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the device
time of the ``als_dst_moments`` kernel on the busiest device (its events in
the trace's ``XLA Ops`` line, overlaps counted once).

Required work, never issued work: pad slots, the lanes a tile pads to and
the gathered rows' second pass are none, so the share cannot pass 100%.
The gather that feeds the kernel is an XLA operation of its own and is not
in the kernel's time.  Nothing without a device trace, without the kernel
in it, or with an adapter that does not price the moments."""

from lib import trace_reduce

KERNEL = "als_dst_moments"


def kernel_roofline(ctx, kernel, work_of):
    """100 x least time of the window's ``work_of`` over the device time
    of the events whose name holds ``kernel``; None where there is none."""
    tr, work = ctx.trace, getattr(ctx.adapter, work_of, None)
    if tr is None or ctx.peaks is None or work is None or not ctx.good_fits:
        return None
    dev = tr.busiest()
    spans = trace_reduce.merge(
        (a, b) for a, b, name in tr.device_ops[dev] if kernel in name
    )
    busy = trace_reduce.total(trace_reduce.clip(spans, *tr.window))
    if busy <= 0:
        return None
    least = sum(
        ctx.least_time_s(work(ctx.cfg, ctx.rows, f["info"]))[0]
        for f in ctx.good_fits
    )
    return 100.0 * least / busy


def read(ctx):
    return kernel_roofline(ctx, KERNEL, "moments_work")
