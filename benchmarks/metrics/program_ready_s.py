"""Compile: ``oap_program_ready_seconds_total`` when the window closes: the
seconds this process spent making programs ready — trace, lowering, and the
backend compile or the load from the persistent cache — from the program's
ledger (``progcache.program_ledger()`` names each).  With ``window_compiles``
0 all of it was paid before the window, inside ``setup_s``: it is the
program's share of set-up beside the benchmark's own table generation.
Nothing where the program has no such counter (before PR 35)."""

from lib import program_counters


def read(ctx):
    return program_counters.total("oap_program_ready_seconds_total")
