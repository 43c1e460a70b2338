"""Scheduler: 90th percentile of the time each request due in the window
spent queued before its first admission (the program's lifecycle span;
one still queued at the window's end counts until then), in s."""
from bench.readers import due_in_window


def read(ctx):
    waits = []
    for r in due_in_window(ctx):
        rec = ctx.engine.lifecycle(r.handle)
        span = next((s for s in rec.spans if s.phase == "queued"), None) \
            if rec is not None else None
        if span is None:
            continue
        end = span.t1 if span.t1 is not None else ctx.window[1]
        waits.append(min(end, ctx.window[1]) - span.t0)
    return ctx.yardstick.percentile(waits, 90) if waits else None
