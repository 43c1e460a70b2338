"""Kernels: ``spu_attn_decode``'s share of its roofline -- the floor of
every shared-block application's decode attention over the live rows of
each traced decode step (``bench/yardstick_zamba2.py``: K/V read to each
row's length at MX8 size, a shared page once) over the kernel's device
time in those steps, in %."""
from bench import yardstick_zamba2
from bench.readers import decode_share


def read(ctx):
    return decode_share(
        ctx, lambda rows: yardstick_zamba2.attn_decode_step(ctx.model, rows)
        + ("attn_decode",))
