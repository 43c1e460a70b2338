"""Model step, decode: mean device time of one call of the paged decode
program (``pool.decode`` -> ``paged_decode_step``) in the trace, in ms."""
from bench.readers import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
