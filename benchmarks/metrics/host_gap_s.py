"""Dispatch: mean over the window's fits of the seconds, inside the
configuration's ``phases`` other than ``table_convert``, in which the host
thread was NOT waiting for the device in a ``fetch`` (device results) or a
``land`` (uploaded bytes): each phase's wall minus every path below it whose
last part is one of the two.  What is left is the host's Python, its launches
and its decisions between a fetch and the next launch, and it should move with
the trace's ``idle_gaps`` seconds a fit in those phases.  The two differ where
the device still runs what was launched earlier in the same gap, most of all
where the runtime holds a launch until the device is done (the program's
``launch`` leaves show it: ``init_centers/rounds/launch`` is 41 ms a fit at
2^22 rows, 24 of them held, against 5 ms at 2^21; README.seams.md).
Nothing where no fit recorded a ``fetch`` leaf (a program from before PR 35:
its phases' walls are no gap)."""

WAITS = ("fetch", "land")


def gap_s(phases, names):
    """One fit's host gap over the phases ``names``, from its flat
    ``{path: seconds}`` view; None where it has no ``fetch`` leaf."""
    waited = {
        p: s for p, s in phases.items()
        if "/" in p and p.rsplit("/", 1)[1] in WAITS
    }
    if not any(p.endswith("/fetch") for p in waited):
        return None
    return sum(
        phases[name]
        - sum(s for p, s in waited.items() if p.startswith(name + "/"))
        for name in names if name in phases
    )


def read(ctx):
    names = [p for p in ctx.cfg["phases"] if p != "table_convert"]
    gaps = [gap_s(f["info"].get("phases", {}), names) for f in ctx.good_fits]
    gaps = [g for g in gaps if g is not None]
    return sum(gaps) / len(gaps) if gaps else None
