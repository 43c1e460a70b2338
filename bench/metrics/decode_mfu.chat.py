"""Model step, decode: the whole step's share of the chip's peak -- its
floor (operations over peak FLOP/s or bytes over peak bandwidth, the
benchmark's count for the rows it decoded) over its device time, in %."""
from bench.readers import decode_mfu


def read(ctx):
    return decode_mfu(ctx)
