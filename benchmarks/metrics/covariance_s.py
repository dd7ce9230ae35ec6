"""Kernels: mean ``covariance`` phase wall: the two moments passes (column
sums, then the centred Gram) from their launch until the covariance is READY.
Since PR 31 the phase waits for it (``block_until_ready``); a program from
before leaves the phase at the launch and the Gram's device time lands in
``eigh_s`` (the reading is then the launch alone, a few milliseconds)."""


def read(ctx):
    return ctx.phase_mean_s("covariance")
