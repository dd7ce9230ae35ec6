"""Kernels: ``als_iterations`` phase wall over the iterations the fit ran,
mean over the window's fits, in milliseconds.  An iteration is a user
half-update and an item half-update; the phase also holds the upload of the
initial factors and the fetch of the final ones (its ``fetch`` leaf)."""

PHASE = "als_iterations"


def read(ctx):
    per = [
        f["info"]["phases"][PHASE] / f["info"]["iterations"]
        for f in ctx.good_fits
        if f["info"].get("iterations") and PHASE in f["info"].get("phases", {})
    ]
    return 1e3 * sum(per) / len(per) if per else None
