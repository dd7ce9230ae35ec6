"""Kernels: the least time the chip could take for the window's moments
passes and eigensolves together (``estimators/pca.phase_work``: 2nd^2 + nd
operations and 4nd bytes, plus 9d^3) over the device-busy time from the
start of each ``covariance`` host annotation to the end of the ``eigh`` one
that follows (``covariance`` does not end in a host fetch, so its device work
may finish under ``eigh``), on the busiest device.

Tied to the phases, not to a kernel's name.  Where the trace holds device
operations but not these annotations, the two phases' walls from
``summary.timings`` divide instead (the share then reads low, never high).
Nothing without a device trace."""

PHASES = ("covariance", "eigh")


def read(ctx):
    tr = ctx.trace
    fits = [f for f in ctx.good_fits
            if all(p in f["info"].get("phases", {}) for p in PHASES)]
    if tr is None or ctx.peaks is None or not fits:
        return None
    least = 0.0
    for f in fits:
        w = ctx.adapter.phase_work(ctx.cfg, ctx.rows, f["info"])
        least += sum(ctx.least_time_s(w[p])[0] for p in PHASES)
    busy, spanned = tr.busy_between(tr.busiest(), *PHASES)
    if spanned <= 0:
        busy = sum(f["info"]["phases"][p] for f in fits for p in PHASES)
    return 100.0 * least / busy if busy > 0 else None
