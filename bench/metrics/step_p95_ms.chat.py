"""Engine step loop: 95th percentile of the durations of the program's
``serve.step`` spans (its trace ring, host clock) that start in the window,
in ms."""
from bench.spans import ring_spans


def read(ctx):
    took = [s["t1"] - s["t0"] for s in ring_spans(ctx, "serve.step")]
    return 1e3 * ctx.yardstick.percentile(took, 95) if took else None
