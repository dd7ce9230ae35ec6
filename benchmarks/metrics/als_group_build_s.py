"""Staging: mean over the window's fits of the ``table_convert/group_edges``
sub-span: the host seconds in which the ratings are counted by destination
(one pass a side, which the blow-up guard and the route plan read too) and
sorted and padded into the two grouped layouts, the chip idle.  The span's
``attrs["threads"]`` says over how many host threads.  Nothing where no fit
recorded the sub-span (a program from before PR 38)."""


def read(ctx):
    return ctx.phase_mean_s("table_convert/group_edges")
