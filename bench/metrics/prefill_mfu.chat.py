"""Model step, prefill: the prefill programs' operations (the benchmark's
count at each call's bucket length) over their device time and the chip's
peak FLOP/s, in %.  A prefill call is the longest device program that
starts inside a request's ``prefill`` lifecycle span (the jitted prefill
has no name of its own in the trace)."""
from bench.readers import DECODE


def _bucket(n, pool):
    s0 = min(n, pool["prefill_chunk"])
    fits = [b for b in pool["prefill_buckets"] if 0 < b <= s0]
    return max(fits) if fits else s0


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.trace_offset is None:
        return None
    calls = [c for c in ctx.tracecut.program_calls(ctx.events,
                                                   ctx.trace_window)
             if c[0] != DECODE]
    flops = took = 0.0
    for r in ctx.client.records:
        rec = ctx.engine.lifecycle(r.handle)
        for s in (rec.spans if rec is not None else []):
            if s.phase != "prefill" or s.t1 is None:
                continue
            a, b = s.t0 + ctx.trace_offset, s.t1 + ctx.trace_offset
            inside = [c for c in calls if a <= c[1] < b]
            if inside:
                _, c0, c1 = max(inside, key=lambda c: c[2] - c[1])
                n = _bucket(len(r.req.prompt), ctx.mix["pool"])
                flops += ctx.yardstick.prefill(ctx.model, n)[0]
                took += c1 - c0
    if took <= 0:
        return None
    return 100.0 * flops / (took * ctx.peaks["flops_per_s"])
