"""Attention K/V: mean of the ``kv_tokens`` arg (the K/V positions one
shared-block application's attention reads over the step's live rows, a
shared page once) over the program's ``serve.step`` spans that decoded
rows and started in the traced part of the window.  None where the
program's spans carry no such arg."""
from bench.spans import ring_spans


def read(ctx):
    if ctx.trace_window is None or ctx.trace_offset is None:
        return None
    t0 = ctx.trace_window[0] - ctx.trace_offset       # on the host clock
    vals = [s["args"]["kv_tokens"] for s in ring_spans(ctx, "serve.step")
            if s["t0"] >= t0 and s["args"].get("rows")
            and "kv_tokens" in s["args"]]
    return sum(vals) / len(vals) if vals else None
