"""Model step, prefill, of the hybrid schedule: the prefill programs'
operations (``bench/yardstick_zamba2.py``'s count at each call's bucket
length, causal attention in every shared-block application) over their
device time and the chip's peak FLOP/s, in %.  The calls are found as
``prefill_mfu.chat`` finds them."""
import os
from types import SimpleNamespace

from bench import yardstick_zamba2
from bench.harness import load_module

_CHAT = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "prefill_mfu.chat.py"),
                    "bench_metric_prefill_mfu_chat_base")


def read(ctx):
    return _CHAT.read(SimpleNamespace(**{**vars(ctx),
                                         "yardstick": yardstick_zamba2}))
