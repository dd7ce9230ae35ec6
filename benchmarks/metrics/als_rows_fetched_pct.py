"""Kernels: the share of the slots a fit's moments walk whose source row
is read, ``100 x rows_fetched / slots_walked`` from the attributes of the
fit's ``als_iterations`` span (``info["iterations_span"]`` of
``estimators/als_implicit_r100.py``), over the window's fits.

``slots_walked`` is every slot of the chunks of groups the
destination-blocked route walks (chunks x groups a chunk x slots a
group), which a gather of whole chunks reads; ``rows_fetched`` the rows
the moments kernel copies, each group's slots up to its last rating.
Nothing where no fit's span carries both (another route, or a program
that does not count them)."""


def read(ctx):
    pairs = [
        (s["rows_fetched"], s["slots_walked"])
        for s in (f["info"].get("iterations_span") or {} for f in ctx.good_fits)
        if "rows_fetched" in s and s.get("slots_walked")
    ]
    if not pairs:
        return None
    return 100.0 * sum(a for a, _ in pairs) / sum(b for _, b in pairs)
