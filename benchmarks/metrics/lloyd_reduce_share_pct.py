"""Collectives: 100 * the time of collective operations inside the
``lloyd_loop`` host annotations over the device-busy time there, on the
busiest device: the reduction's share of the phase it sits in, hidden or
not.  A data-parallel Lloyd iteration ends in one all-reduce of the (k, d)
sums and (k,) counts, so this is what a faster reduction (a ring, a packed
buffer) could win at most.  Nothing without a device trace, the annotation,
or a collective inside it."""

from lib import collectives, trace_reduce

PHASE = "lloyd_loop"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    dev = tr.busiest()
    busy, spanned = tr.busy_inside(dev, [PHASE])
    if spanned <= 0 or busy <= 0:
        return None
    reduce_s = sum(
        trace_reduce.total(
            trace_reduce.clip(collectives.collective_intervals(tr, dev), a, b)
        )
        for a, b in trace_reduce.clip(tr.spans_named([PHASE]), *tr.window)
    )
    if reduce_s <= 0:
        return None
    return 100.0 * reduce_s / busy
