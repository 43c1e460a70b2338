"""``repro.obs`` -- observability for the serving stack.

One :class:`Observability` object per engine bundles the four pieces the
stack threads through itself:

  * :class:`~repro.obs.metrics.MetricsRegistry` -- labeled counters /
    gauges / histograms; ``Engine.stats()`` is a schema-stable view over
    it and ``prometheus_text()`` renders it for scraping;
  * :class:`~repro.obs.trace.TraceBuffer` -- a bounded ring of per-step
    structured events (engine-step spans, admissions, evictions, forks,
    pool counters), exportable as Chrome-trace JSON (Perfetto) or JSONL;
    ``Observability.span`` writes a span into it and, while a
    ``jax.profiler`` session runs, into the profiler's trace too;
  * :class:`~repro.obs.lifecycle.LifecycleTracker` -- per-request phase
    spans (queued -> prefill -> ingest -> decode -> spilled -> terminal)
    with exact TTFT / TPOT / queue-delay / preemption-cost per request;
  * :class:`~repro.obs.recompile.RecompileWatcher` -- wraps the jitted
    steppers and records every fresh trace/compile with the changed
    abstract-shape signature.

Usage (the serving engines do all of this internally):

    obs = Observability()
    fn = obs.wrap_jit(jax.jit(step), "engine.decode")
    ...
    obs.save_trace("out.json")          # load in https://ui.perfetto.dev
    print(obs.prometheus_text())
"""
from __future__ import annotations

from repro.obs.lifecycle import (PHASES, LifecycleTracker, PhaseSpan,
                                 RequestRecord)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.recompile import (RecompileEvent, RecompileWatcher,
                                 WatchedFunction)
from repro.obs.schema import trace_features, validate_chrome_trace
from repro.obs.trace import Span, TraceBuffer

__all__ = [
    "Observability",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "TraceBuffer", "Span",
    "LifecycleTracker", "RequestRecord", "PhaseSpan", "PHASES",
    "RecompileWatcher", "RecompileEvent", "WatchedFunction",
    "validate_chrome_trace", "trace_features",
]


class Observability:
    """The per-engine bundle: metrics + trace + lifecycle + recompiles."""

    def __init__(self, trace_capacity: int = 65536):
        self.metrics = MetricsRegistry()
        self.tracer = TraceBuffer(capacity=trace_capacity)
        self.lifecycle = LifecycleTracker(self.tracer, self.metrics)
        self.recompiles = RecompileWatcher(self.tracer, self.metrics)

    def wrap_jit(self, fn, name: str) -> WatchedFunction:
        """Put the recompile watcher around a jitted callable."""
        return self.recompiles.wrap(fn, name)

    def span(self, name: str, cat: str = "span", **args) -> Span:
        """``with obs.span("serve.prefill", rid=3, tokens=64):`` -- one
        ``X`` event on the engine track, and a profiler annotation of the
        same name and args while open (see :class:`~repro.obs.trace.Span`)."""
        return Span(self.tracer, name, cat, **args)

    def save_trace(self, path: str) -> None:
        """Chrome-trace JSON (or JSONL for ``*.jsonl`` paths)."""
        self.tracer.save(path)

    def prometheus_text(self) -> str:
        return self.metrics.prometheus_text()
