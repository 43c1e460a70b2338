"""Device: share of the traced time in which at least one request was
running and no operation ran on the chip, in %."""
from bench.readers import idle_share_running


def read(ctx):
    return idle_share_running(ctx)
