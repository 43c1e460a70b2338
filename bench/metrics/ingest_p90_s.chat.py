"""Engine prompt ingestion: 90th percentile, over the requests due in the
window, of the time each spent in the program's ``ingest`` lifecycle phase:
in the decode batch before its first token, feeding the prompt tokens its
prefill left one a step.  A request without such a tail reads 0; one still
ingesting when the window closes counts until then.  In s."""
from bench.readers import due_in_window


def read(ctx):
    from repro.obs import PHASES
    if "ingest" not in PHASES:
        return None                 # the program has no such phase
    w1 = ctx.window[1]
    times = []
    for r in due_in_window(ctx):
        rec = ctx.engine.lifecycle(r.handle)
        if rec is None:
            continue
        times.append(sum(min(s.t1 if s.t1 is not None else w1, w1) - s.t0
                         for s in rec.spans
                         if s.phase == "ingest" and s.t0 < w1))
    return ctx.yardstick.percentile(times, 90) if times else None
