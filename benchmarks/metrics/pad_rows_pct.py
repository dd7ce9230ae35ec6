"""Staging: the share of the device table's rows that are padding, in %:
``(padded_rows - valid_rows) / padded_rows`` from the attributes of
``table_convert/upload``, mean over the window's fits (every fit of a cell
reads the same).  The kernels walk the pad rows like any other (their mask
is 0), so this is the share of the round's and the walk's device time that
no row of the caller's needs: 25.49 for 3,125,000 rows on the 4,194,304
bucket.  Nothing where no fit's span carries the two counts."""


def read(ctx):
    shares = []
    for f in ctx.good_fits:
        s = f["info"].get("staging", {})
        if s.get("padded_rows"):
            shares.append(100.0 * (s["padded_rows"] - s["valid_rows"]) / s["padded_rows"])
    return sum(shares) / len(shares) if shares else None
