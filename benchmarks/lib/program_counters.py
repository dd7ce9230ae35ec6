"""The program's own process-wide counters (``oap_mllib_tpu.telemetry``'s
metrics registry), read by name for the readers whose source is
``program_counter``."""


def total(name):
    """Sum over the label sets of the counter family ``name``; None where the
    program has no such series (a program from before the PR that added it)."""
    from oap_mllib_tpu import telemetry

    series = telemetry.snapshot().get(name)
    if series is None:
        return None
    return sum(v["sum"] if isinstance(v, dict) else v for v in series.values())
