"""Staging: the host-to-device rate of a fit's upload, in GB/s (1e9 bytes):
the table's and its mask's bytes over the mean ``table_convert/upload``
sub-span wall.  The bytes are reckoned from the configuration (``d``, its
``dtype``, float32 where it states none) and the cell's rows, a mask item a
row, not read from the program: ``rows * (d + 1) * itemsize``.  On a v5e
host the fast path reads about 10 on one chip and 24.5 on four, the slow one
(more than about 4.3 GB in flight at once) 0.5-2.  Nothing where no fit
recorded the sub-span."""

ITEMSIZE = {"float32": 4, "float64": 8, "bfloat16": 2}


def read(ctx):
    wall = ctx.phase_mean_s("table_convert/upload")
    if not wall:
        return None
    itemsize = ITEMSIZE[ctx.cfg.get("dtype", "float32")]
    return ctx.rows * (ctx.cfg["d"] + 1) * itemsize / wall / 1e9
