"""Kernels: the least time the chip could take for the window's Lloyd
iterations and final cost passes (``estimators/kmeans.phase_work``: per
iteration 2nkd + 2nd operations and 4nd bytes; the larger of operations over
peak FLOP/s and bytes over peak bytes/s) over the device-busy time inside
the ``lloyd_loop`` host annotations of the trace, on the busiest device.

The share is tied to the PHASE, not to a kernel's name: whatever implements
the loop is held to the same work.  Where the trace holds device operations
but no ``lloyd_loop`` annotation, the phase's wall from ``summary.timings``
divides instead (an upper bound of the device time: the share reads low,
never high).  Nothing without a device trace."""

PHASE = "lloyd_loop"


def read(ctx):
    tr = ctx.trace
    fits = [f for f in ctx.good_fits if PHASE in f["info"].get("phases", {})]
    if tr is None or ctx.peaks is None or not fits:
        return None
    work = [ctx.adapter.phase_work(ctx.cfg, ctx.rows, f["info"])[PHASE] for f in fits]
    least = sum(ctx.least_time_s(w)[0] for w in work)
    busy, spanned = tr.busy_inside(tr.busiest(), [PHASE])
    if spanned <= 0:
        busy = sum(f["info"]["phases"][PHASE] for f in fits)
    return 100.0 * least / busy if busy > 0 else None
