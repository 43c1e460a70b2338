"""Kernels: ``spu_state_update``'s share of its roofline -- the floor of
every live row's state update in each traced decode step over the kernel's
device time in those steps, in %."""
from bench.readers import decode_share, step_rows_state


def read(ctx):
    return decode_share(ctx, lambda rows: step_rows_state(ctx, rows))
