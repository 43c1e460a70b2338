"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.

A reader gets a context with the client's records (host clock), the
program's lifecycle spans, the reduced trace and its events, the model's
sizes and the chip's peaks; it returns a number or None when it finds
nothing to read.  Host-clock stamps map onto the trace's clock by the
offset of the ``bench.window`` annotation (``ctx.trace_offset``).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

DECODE = "decode_impl"


def due_in_window(ctx) -> list:
    w0, w1 = ctx.window
    return [r for r in ctx.client.records if w0 <= r.due < w1]


def traced_steps(ctx) -> List[Tuple[float, float, float, float, list]]:
    """(call t0, call t1, step t0, step t1, rows) of each decode program call
    in the trace, matched to the client step (host clock) it ran in."""
    if ctx.trace is None or ctx.trace_offset is None:
        return []
    calls = [(a, b) for _, a, b in ctx.tracecut.program_calls(
        ctx.events, ctx.trace_window, DECODE)]
    steps = [(a + ctx.trace_offset, b + ctx.trace_offset, rows)
             for a, b, rows in ctx.client.steps]
    out, j = [], 0
    for c0, c1 in calls:
        while j < len(steps) and steps[j][1] < c0:
            j += 1
        if j < len(steps) and steps[j][0] <= c0 and steps[j][2]:
            out.append((c0, c1) + steps[j])
    return out


def kernel_time_in(ctx, kind: str, spans) -> float:
    """Seconds of ``spu_<kind>`` ops that start inside ``spans``."""
    planes = ctx.tracecut.device_planes(ctx.events)
    if not planes:
        return 0.0
    starts = sorted(spans)
    total = 0.0
    for e in ctx.events:
        if (e["plane"] == planes[0] and e["line"] == ctx.tracecut.OPS_LINE
                and ctx.tracecut.kernel_kind(e["name"]) == kind):
            for a, b in starts:
                if a <= e["t0"] < b:
                    total += e["t1"] - e["t0"]
                    break
    return total


def decode_share(ctx, part) -> Optional[float]:
    """Floor over measured time, in %, summed over traced decode calls.
    ``part(rows) -> (flops, bytes, kernel kind or None)``."""
    steps = traced_steps(ctx)
    if not steps or ctx.peaks is None:
        return None
    floor, spans, kind = 0.0, [], None
    for c0, c1, _, _, rows in steps:
        flops, nbytes, kind = part(rows)
        floor += ctx.yardstick.floor_seconds(flops, nbytes, ctx.peaks)
        spans.append((c0, c1))
    took = (sum(b - a for a, b in spans) if kind is None
            else kernel_time_in(ctx, kind, spans))
    return 100.0 * floor / took if took > 0 else None


def step_rows_state(ctx, rows):
    s = ctx.yardstick.shapes(ctx.model)
    f, b = ctx.yardstick.state_update_call(len(rows), s["H"], s["N"], s["P"])
    return s["L"] * f, s["L"] * b, "state_update"


def decode_step_ms(ctx) -> Optional[float]:
    steps = traced_steps(ctx)
    if not steps:
        return None
    return 1e3 * sum(c1 - c0 for c0, c1, *_ in steps) / len(steps)


def decode_mfu(ctx) -> Optional[float]:
    return decode_share(
        ctx, lambda rows: ctx.yardstick.decode_step(ctx.model, rows)
        + (None,))


def idle_share_running(ctx) -> Optional[float]:
    """Idle share over the traced time in which a request was running."""
    if ctx.trace is None or ctx.trace_offset is None:
        return None
    lo, hi = ctx.trace_window
    ivs = []
    for r in ctx.client.records:
        a = r.sent + ctx.trace_offset
        b = (r.done if r.done is not None else ctx.window[1]) \
            + ctx.trace_offset
        a, b = max(a, lo), min(b, hi)
        if b > a:
            ivs.append((a, b))
    ivs = ctx.tracecut._union(ivs)
    total = sum(b - a for a, b in ivs)
    if total <= 0:
        return None
    busy = sum(ctx.trace["busy_in"](a, b) for a, b in ivs)
    return 100.0 * (1.0 - busy / total)
