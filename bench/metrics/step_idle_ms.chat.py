"""Engine step host work: mean time, over the traced ``serve.step`` spans
(the profiler's clock), in which no operation ran on the device inside the
span, in ms."""
from bench import spans


def read(ctx):
    if ctx.trace_window is None:
        return None
    busy = spans.busy_intervals(ctx.events, ctx.trace_window)
    steps = [e for e in spans.profiler_spans(ctx.events, ctx.trace_window,
                                             "serve.step")
             if e["t1"] <= ctx.trace_window[1]]
    if not busy or not steps:
        return None
    idle = [sum(spans.idle_in(p, e["t0"], e["t1"]) for p in busy) / len(busy)
            for e in steps]
    return 1e3 * sum(idle) / len(idle)
