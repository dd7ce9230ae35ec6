"""Compile: ``oap_programs_compiled_total`` when the window closes: backend
compiles the persistent cache did NOT serve.  0 in a warm checkout, the
fit's program count in a checkout's first run — which is what tells a slow
``setup_s`` that compiled from one that did not.  Nothing where the program
has no such counter (before PR 35)."""

from lib import program_counters


def read(ctx):
    return program_counters.total("oap_programs_compiled_total")
