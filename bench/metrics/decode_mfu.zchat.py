"""Model step, decode, of the hybrid schedule: the whole step's share of
the chip's peak -- its floor (operations over peak FLOP/s or bytes over
peak bandwidth, ``bench/yardstick_zamba2.py``'s count for the rows it
decoded, each shared block's weights read once per application) over its
device time, in %."""
from types import SimpleNamespace

from bench import yardstick_zamba2
from bench.readers import decode_mfu


def read(ctx):
    return decode_mfu(SimpleNamespace(**{**vars(ctx),
                                         "yardstick": yardstick_zamba2}))
