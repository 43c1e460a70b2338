"""The program's own ``serve.*`` spans, for the per-layer metric readers.

The engine writes each span twice (``repro.obs.Span``): as an ``X`` event
in its trace ring (``ctx.engine.obs.tracer``), on the host's
``perf_counter`` clock and with its args, and, while the profiler runs, as
a host event of the same name in the profiler's trace (``ctx.events``), on
the clock of the device ops.  A program without these spans gives empty
lists here, and the readers then return None.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import tracecut

PREFIX = "serve."


def profiler_spans(events: Sequence[dict], window: Tuple[float, float],
                   name: Optional[str] = None) -> List[dict]:
    """``serve.*`` host events of the profiler's trace that start inside
    ``window``; only those called ``name`` when given."""
    lo, hi = window
    return sorted((e for e in events
                   if "/device:" not in e["plane"]
                   and e["name"].startswith(PREFIX)
                   and (name is None or e["name"] == name)
                   and lo <= e["t0"] < hi), key=lambda e: e["t0"])


def ring_spans(ctx, name: str) -> List[dict]:
    """``name`` spans of the engine's trace ring that start inside the
    measured window: ``{"t0", "t1", "args"}`` on the host clock."""
    tracer = ctx.engine.obs.tracer
    base = -tracer.ts_of(0.0) * 1e-6          # the ring's zero, in seconds
    w0, w1 = ctx.window
    out = []
    for e in tracer.events():
        if e.get("ph") != "X" or e.get("name") != name:
            continue
        t0 = base + e["ts"] * 1e-6
        if w0 <= t0 < w1:
            out.append({"t0": t0, "t1": t0 + e["dur"] * 1e-6,
                        "args": e.get("args", {})})
    return out


def busy_intervals(events: Sequence[dict], window: Tuple[float, float]
                   ) -> List[List[Tuple[float, float]]]:
    """Per device plane, the union of its op intervals inside ``window``."""
    lo, hi = window
    return [tracecut._union(tracecut._clip(
        [(e["t0"], e["t1"]) for e in events
         if e["plane"] == p and e["line"] == tracecut.OPS_LINE], lo, hi))
        for p in tracecut.device_planes(events)]


def idle_gaps(busy: List[Tuple[float, float]], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_in(busy: List[Tuple[float, float]], a: float, b: float) -> float:
    """Seconds of [a, b) in which no op of one plane ran."""
    i = max(bisect.bisect_right(busy, (a, a)) - 1, 0)
    covered = 0.0
    for s, t in busy[i:]:
        if s >= b:
            break
        covered += max(0.0, min(t, b) - max(s, a))
    return (b - a) - covered


def idle_by_span(events: Sequence[dict], window: Tuple[float, float]
                 ) -> Dict[str, float]:
    """Device-idle seconds inside ``window`` by the innermost ``serve.*``
    host span over each idle gap's midpoint, or ``outside``; averaged over
    the device planes.  Empty when the trace has no device plane."""
    busy = busy_intervals(events, window)
    if not busy:
        return {}
    spans = profiler_spans(events, (float("-inf"), window[1]))
    starts = [e["t0"] for e in spans]
    reach, far = [], float("-inf")       # latest end among spans[:j + 1]
    for e in spans:
        far = max(far, e["t1"])
        reach.append(far)
    out: Dict[str, float] = defaultdict(float)
    for plane in busy:
        for a, b in idle_gaps(plane, *window):
            mid = 0.5 * (a + b)
            what = "outside"
            # spans nest: the innermost one over ``mid`` is the latest to
            # start before it among those still open at it
            j = bisect.bisect_right(starts, mid) - 1
            while j >= 0 and reach[j] > mid:
                if spans[j]["t1"] > mid:
                    what = spans[j]["name"]
                    break
                j -= 1
            out[what] += (b - a) / len(busy)
    return dict(out)
