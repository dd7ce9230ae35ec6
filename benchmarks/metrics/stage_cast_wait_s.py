"""Staging: mean over the window's fits of the seconds the sender of a table
that is cast under its upload stood waiting for a block's cast
(``table_convert/upload``'s ``attrs["cast_wait_s"]``): the part of the cast
that the transfers did not hide.  0 where the caller's array goes up as it
is; nothing where no fit's span carries the attribute (a program from
before PR 33, an adapter that does not hand the attributes on)."""


def read(ctx):
    waits = [
        f["info"]["staging"]["cast_wait_s"] for f in ctx.good_fits
        if "cast_wait_s" in f["info"].get("staging", {})
    ]
    return sum(waits) / len(waits) if waits else None
