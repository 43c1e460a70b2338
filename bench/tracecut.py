"""Reduce a profiler trace to what the per-layer metrics read.

:func:`load_events` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain events ``{"plane", "line", "name", "t0", "t1"}`` (seconds, one
clock for host and device).  :func:`reduce` works on those alone, so the
arithmetic is tested on a small recorded event list.

From the device planes it takes the op line (``XLA Ops``) for busy time,
kernels and the top ops, and the module line (``XLA Modules``) for the time
of each jitted program.  From the host planes it takes the benchmark's own
``TraceAnnotation`` spans (``bench.<what>``), which name what the host was
doing in each idle gap of the device.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


def load_events(trace_dir: str) -> List[dict]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                out.append({"plane": plane.name, "line": line.name,
                            "name": short_name(ev.name), "t0": t0,
                            "t1": t0 + ev.duration_ns * 1e-9})
    return out


def short_name(name: str) -> str:
    """An op event's name is its whole HLO instruction
    (``%fusion.3 = f32[..] fusion(..)``); keep what precedes `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _base(name: str) -> str:
    """Op name without XLA's suffixes (``copy.12.remat2`` -> ``copy``)."""
    return re.sub(r"\.\d.*$", "", name)


def _self_times(ops: Sequence[dict]) -> List[Tuple[str, float]]:
    """(op, seconds) of each op less the ops nested inside it: a loop's
    event spans its body's ops on the same line.  An op that starts inside
    another is nested in it (its end, clipped to the outer end, may lie a
    rounding past it)."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []           # [name, t1, own length, nested time]
    for e in sorted(ops, key=lambda e: (e["t0"], e["t0"] - e["t1"])):
        while stack and e["t0"] >= stack[-1][1]:
            name, _, length, inner = stack.pop()
            out.append((name, length - inner))
        t1 = min(e["t1"], stack[-1][1]) if stack else e["t1"]
        if stack:
            stack[-1][3] += t1 - e["t0"]
        stack.append([_base(e["name"]), t1, t1 - e["t0"], 0.0])
    out += [(name, length - inner) for name, _, length, inner in stack]
    return out


def _program(name: str) -> str:
    """Jitted program of a module event (``jit__decode_impl(123)``)."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"^jit_+", "", name)


def kernel_kind(name: str) -> Optional[str]:
    """``state_update`` for an op named ``spu_state_update.27``."""
    m = re.match(r"spu_([a-z_]+?)(\.[\w-]+)*$", name)
    return m.group(1) if m else None


def device_planes(events: Sequence[dict]) -> List[str]:
    return sorted({e["plane"] for e in events
                   if e["line"] == OPS_LINE and "/device:" in e["plane"]})


def reduce(events: Sequence[dict], window: Optional[Tuple[float, float]]
           = None) -> Optional[dict]:
    """Busy and idle time, kernel and program times, and the breakdown.

    ``window`` (t0, t1) on the trace's clock defaults to the span of the
    host annotation ``bench.window``.  Returns None when no device op ran.
    """
    planes = device_planes(events)
    if not planes:
        return None
    host = [e for e in events if "/device:" not in e["plane"]
            and e["name"].startswith(HOST_PREFIX)]
    if window is None:
        wins = [e for e in host if e["name"] == HOST_PREFIX + "window"]
        window = ((wins[0]["t0"], wins[0]["t1"]) if wins else
                  (min(e["t0"] for e in events),
                   max(e["t1"] for e in events)))
    lo, hi = window
    span = hi - lo

    busy_s, idle_gaps = 0.0, []
    ops = defaultdict(float)
    kernels = defaultdict(lambda: [0.0, 0])
    programs = defaultdict(lambda: [0.0, 0])
    for plane in planes:
        mine = [e for e in events if e["plane"] == plane]
        op_iv = [(e["t0"], e["t1"]) for e in mine if e["line"] == OPS_LINE]
        busy = _union(_clip(op_iv, lo, hi))
        busy_s += sum(b - a for a, b in busy) / len(planes)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle_gaps += [(edges[i], edges[i + 1])
                      for i in range(0, len(edges), 2)
                      if edges[i + 1] > edges[i]]
        inside = [e for e in mine if lo <= e["t0"] < hi]
        for op, d in _self_times([e for e in inside
                                  if e["line"] == OPS_LINE]):
            ops[op] += d / len(planes)
        for e in inside:
            d = e["t1"] - e["t0"]
            kind = kernel_kind(e["name"])
            if e["line"] == OPS_LINE and kind:
                kernels[kind][0] += d / len(planes)
                kernels[kind][1] += 1
            elif e["line"] == MODULES_LINE:
                p = _program(e["name"])
                programs[p][0] += d / len(planes)
                programs[p][1] += 1

    # attribute each idle gap to the innermost host span covering its middle
    by_host = defaultdict(float)
    spans = sorted(host, key=lambda e: e["t1"] - e["t0"])
    for a, b in idle_gaps:
        mid = 0.5 * (a + b)
        what = next((e["name"][len(HOST_PREFIX):] for e in spans
                     if e["name"] != HOST_PREFIX + "window"
                     and e["t0"] <= mid < e["t1"]), "outside")
        by_host[what] += (b - a) / len(planes)

    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": span,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / span if span > 0 else None,
        "kernels": {k: {"seconds": v[0], "calls": v[1]}
                    for k, v in kernels.items()},
        "programs": {k: {"seconds": v[0], "calls": v[1]}
                     for k, v in programs.items()},
        "busy_in": lambda t0, t1: _busy_in(events, planes, t0, t1),
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(by_host)},
    }


def _busy_in(events, planes, lo, hi) -> float:
    """Device-busy seconds inside [lo, hi), averaged over the chips."""
    total = 0.0
    for plane in planes:
        iv = [(e["t0"], e["t1"]) for e in events
              if e["plane"] == plane and e["line"] == OPS_LINE]
        total += sum(b - a for a, b in _union(_clip(iv, lo, hi)))
    return total / len(planes)


def program_calls(events: Sequence[dict], window: Tuple[float, float],
                  program: Optional[str] = None
                  ) -> List[Tuple[str, float, float]]:
    """(program, t0, t1) of each jitted program call that starts inside the
    window, on the first device plane; only ``program``'s when given."""
    planes = device_planes(events)
    if not planes:
        return []
    lo, hi = window
    return sorted(((_program(e["name"]), e["t0"], e["t1"]) for e in events
                   if e["plane"] == planes[0] and e["line"] == MODULES_LINE
                   and lo <= e["t0"] < hi
                   and program in (None, _program(e["name"]))),
                  key=lambda c: c[1])
