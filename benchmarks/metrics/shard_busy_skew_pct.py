"""Device: 100 * (compute seconds of the device with most - of the one with
least) / the most, in the traced window, where a device's compute seconds
are those of its operations that are no collective.  In a data-parallel step
that ends in a synchronous all-reduce the device whose shard is done first
waits INSIDE its all-reduce, and the trace books that wait as busy: whole
busy time is equal across the devices by construction (and the enclosing
``%while`` covers the loop on every one), so the straggler shows only once
the collectives are taken out — as more compute on one device and a longer
all-reduce on the others.  Read on the leaves of the ``XLA Ops`` line
(``lib/collectives.py``).  Nothing without a device trace or on a single
device (no other shard to lag behind)."""

from lib import collectives


def read(ctx):
    tr = ctx.trace
    if tr is None or len(tr.devices) < 2:
        return None
    own = [collectives.compute_s(tr, d) for d in tr.devices]
    return 100.0 * (max(own) - min(own)) / max(own) if max(own) > 0 else None
