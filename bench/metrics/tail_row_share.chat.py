"""Decode batch: share of the rows decoded in the window's engine steps that
fed a prompt token rather than generating one (the ``tail_rows`` and
``rows`` args of the program's ``serve.step`` spans), in %."""
from bench.spans import ring_spans


def read(ctx):
    steps = ring_spans(ctx, "serve.step")
    rows = sum(s["args"].get("rows", 0) for s in steps)
    tail = sum(s["args"].get("tail_rows", 0) for s in steps)
    return 100.0 * tail / rows if rows else None
