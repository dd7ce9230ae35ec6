"""Staging: the share of the grouped layouts' slots that hold no rating, in
%: ``(padded - 2 * ratings) / padded`` with ``padded`` =
``padded_edges_user + padded_edges_item`` from the attributes of
``table_convert/group_edges``, mean over the window's fits (every fit of a
cell reads the same).  Each destination's ratings are padded to whole groups
of ``group_size`` slots, and every slot is uploaded and multiplied like any
other (its ``valid`` is 0): this is the share of the upload and of the moment
products that no rating needs.  Nothing where no fit's span carries the
counts."""


def read(ctx):
    shares = []
    for f in ctx.good_fits:
        s = f["info"].get("staging", {})
        padded = s.get("padded_edges_user", 0) + s.get("padded_edges_item", 0)
        if padded and s.get("ratings"):
            shares.append(100.0 * (padded - 2 * s["ratings"]) / padded)
    return sum(shares) / len(shares) if shares else None
