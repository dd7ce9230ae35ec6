"""Kernels: ``lloyd_loop`` phase wall over the iterations the fit ran, mean
over the window's fits, in milliseconds.  The phase also holds the final
cost pass and the fetch of the centres."""


def read(ctx):
    per = [
        f["info"]["phases"]["lloyd_loop"] / f["info"]["num_iter"]
        for f in ctx.good_fits
        if f["info"].get("num_iter") and "lloyd_loop" in f["info"].get("phases", {})
    ]
    return 1e3 * sum(per) / len(per) if per else None
