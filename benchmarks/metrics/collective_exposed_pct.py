"""Collectives: 100 * the seconds in which a collective operation ran on the
busiest device while no other operation did (``Trace.exposed_s``) over the
traced window.  What the all-reduce of the Lloyd moments, the k-means||
round's reductions and the fetches' gathers cost that nothing hides.

Read on the leaves of the ``XLA Ops`` line (``lib/collectives.py``): the
enclosing ``%while`` would otherwise hide every collective of the loop.  An
asynchronous collective is on that line as its ``-start`` and ``-done``; the
wait in ``-done`` is its exposed part.  Nothing without a device trace, and
nothing where the trace holds no collective at all (one chip; a program
that reduces nothing)."""

from lib import collectives


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0:
        return None
    dev = tr.busiest()
    leaf = collectives.leaf_trace(tr)
    if not any(collectives.is_collective(n) for _, _, n in leaf.device_ops[dev]):
        return None
    return 100.0 * leaf.exposed_s(dev, collectives.is_collective) / tr.window_s
