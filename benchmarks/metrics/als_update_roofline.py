"""Kernels: the least time the chip could take for the window's half-updates
(``estimators/als_implicit.phase_work``: per rating the r(r+1) + 2r moment
operations and one read of 12 + 4r bytes, per destination an r^3/3 solve
and its 4r bytes, the Gram 2 n r^2; the larger of operations over peak
FLOP/s and bytes over peak bytes/s) over the device-busy time inside the
``als_iterations`` host annotations of the trace, on the busiest device.

The share is tied to the PHASE and to REQUIRED work, not to a kernel's name
or to what today's layout issues: pad slots, the transposed gather and the
moments carried through HBM are no required work, so the share reads low
while they are there.  At rank 10 the bytes bind (52 B against 130
operations a rating: 0.4 ms of arithmetic beside 8 ms of reads a
half-update of 126M ratings).  Where the trace holds device operations but
no ``als_iterations`` annotation, the phase's wall divides instead (the
share then reads low, never high).  Nothing without a device trace."""

PHASE = "als_iterations"


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
