"""Share of the traced window in which no operation ran on the device
(1 - busy union / window), for the decode cells."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
