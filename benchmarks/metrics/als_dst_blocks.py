"""Estimator: the blocks of destinations a half-update pair walks on the
destination-blocked route, ``blocks_user + blocks_item`` from the
attributes of the fit's ``als_iterations`` span (``info["iterations_span"]``
of ``estimators/als_implicit_r100.py``), mean over the window's fits.
Nothing where no fit's span carries them (another route, or a program
without this one)."""


def read(ctx):
    blocks = [
        s["blocks_user"] + s["blocks_item"]
        for s in (f["info"].get("iterations_span") or {} for f in ctx.good_fits)
        if "blocks_user" in s and "blocks_item" in s
    ]
    return sum(blocks) / len(blocks) if blocks else None
