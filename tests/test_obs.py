"""Observability subsystem: registry semantics, span lifecycle ordering,
Chrome-trace export validity, recompile watcher, engine integration."""
import glob
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.obs import (Observability, MetricsRegistry, TraceBuffer,
                       LifecycleTracker, RecompileWatcher, PHASES,
                       validate_chrome_trace, trace_features)
from repro.obs.metrics import Histogram
from repro.serving.api import Engine, ServeConfig


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_semantics():
    m = MetricsRegistry()
    m.counter("reqs").inc()
    m.counter("reqs").inc(2.5)
    assert m.value("reqs") == 3.5
    with pytest.raises(ValueError):
        m.counter("reqs").inc(-1)
    g = m.gauge("active")
    g.set(4)
    g.dec()
    assert m.value("active") == 3.0
    # untouched metrics read 0.0, never KeyError (schema stability)
    assert m.value("never_written") == 0.0
    assert m.value("reqs", ) == 3.5


def test_labels_partition_families():
    m = MetricsRegistry()
    m.counter("requests_total", status="done").inc(3)
    m.counter("requests_total", status="aborted").inc()
    assert m.value("requests_total", status="done") == 3.0
    assert m.value("requests_total", status="aborted") == 1.0
    assert m.value("requests_total", status="truncated") == 0.0
    # kind / label-set mismatches are bugs, not silent new families
    with pytest.raises(ValueError):
        m.gauge("requests_total", status="done")
    with pytest.raises(ValueError):
        m.counter("requests_total", other="x")


def test_histogram_exact_then_bounded():
    h = Histogram(cap=8)
    xs = [3.0, 1.0, 2.0, 5.0, 4.0]
    for x in xs:
        h.observe(x)
    assert h.count == 5 and h.sum == 15.0 and h.mean == 3.0
    # below the cap the percentile is exact np.percentile of everything
    assert h.percentile(50) == float(np.percentile(xs, 50))
    assert h.percentile(99) == float(np.percentile(xs, 99))
    for x in range(100):
        h.observe(float(x))
    assert h.count == 105            # count/sum stay exact
    assert len(h.samples) < 8        # reservoir stays bounded
    s = h.summary()
    assert set(s) == {"count", "sum", "mean", "p50", "p90", "p99", "max"}


def test_empty_histogram_reads_zero():
    m = MetricsRegistry()
    h = m.histogram("step_s", compile="false")
    assert h.percentile(99) == 0.0 and h.mean == 0.0
    assert m.family_samples("step_s") == []
    assert m.family_count("nope") == 0.0


def test_prometheus_text_renders_all_kinds():
    m = MetricsRegistry()
    m.counter("toks").inc(7)
    m.gauge("live", pool="a").set(2)
    m.histogram("lat_s").observe(0.5)
    text = m.prometheus_text()
    assert "# TYPE toks counter" in text
    assert "toks 7" in text
    assert 'live{pool="a"} 2' in text
    assert "# TYPE lat_s summary" in text
    assert 'lat_s{quantile="0.99"} 0.5' in text
    assert "lat_s_count 1" in text


# ---------------------------------------------------------------------------
# trace buffer
# ---------------------------------------------------------------------------

def test_trace_ring_keeps_metadata_and_counts_drops():
    tr = TraceBuffer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}", cat="x")
    assert tr.dropped == 6
    evs = tr.events()
    # thread_name metadata survives ring eviction
    assert any(e["ph"] == "M" for e in evs)
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    assert obj["otherData"]["dropped_events"] == 6


def test_trace_export_chrome_and_jsonl(tmp_path):
    tr = TraceBuffer()
    tr.complete("step", cat="step", ts=tr.now_us(), dur=100.0, batch=2)
    tr.complete("serve.sync", cat="span", ts=tr.now_us(), dur=80.0,
                parent="serve.step")
    tr.counter("pool", {"occupancy": 0.5})
    tr.async_span("decode", 7, "request", 0.0, 50.0, rid=7)
    p_json, p_jsonl = tmp_path / "t.json", tmp_path / "t.jsonl"
    tr.save(str(p_json))
    tr.save(str(p_jsonl))
    obj = json.loads(p_json.read_text())
    assert validate_chrome_trace(obj) == []
    feats = trace_features(obj)
    assert {"steps", "spans", "phases"} <= feats
    lines = [json.loads(L) for L in p_jsonl.read_text().splitlines()]
    assert len(lines) == len(tr.events())


def test_schema_catches_invalid_traces():
    assert validate_chrome_trace([]) == ["top level must be an object"]
    assert validate_chrome_trace({}) == ["missing traceEvents list"]
    bad = {"traceEvents": [
        {"ph": "X", "name": "no_dur", "pid": 1, "tid": 0, "ts": 0.0},
        {"ph": "b", "name": "open", "cat": "request", "id": "1",
         "pid": 1, "tid": 0, "ts": 0.0},            # never closed
        {"ph": "?", "name": "junk", "pid": 1, "tid": 0, "ts": 0.0},
    ]}
    errs = validate_chrome_trace(bad)
    assert any("dur" in e for e in errs)
    assert any("dangling" in e for e in errs)
    assert any("unknown phase" in e for e in errs)


# ---------------------------------------------------------------------------
# lifecycle spans
# ---------------------------------------------------------------------------

def test_span_chain_complete_and_derived_metrics():
    tr = TraceBuffer()
    m = MetricsRegistry()
    lc = LifecycleTracker(tr, m)
    lc.enqueued(1, t=10.0)
    lc.phase(1, "prefill", t=12.0)
    lc.phase(1, "decode", t=13.0)
    lc.phase(1, "spilled", t=14.0)
    lc.phase(1, "decode", t=16.5)
    lc.first_token(1, t=13.5)
    lc.finish(1, "done", n_tokens=5, t=20.0)
    rec = lc.record(1)
    assert rec.complete_chain()
    assert rec.phase_sequence() == ["queued", "prefill", "decode",
                                    "spilled", "decode"]
    assert rec.queue_delay_s == 2.0
    assert rec.ttft_s == 3.5
    assert rec.preemption_cost_s == 2.5
    assert rec.tpot_s == pytest.approx((20.0 - 13.5) / 4)
    # duplicate phase transition is a no-op, not a new span
    lc2 = LifecycleTracker()
    lc2.enqueued(2, t=0.0)
    lc2.phase(2, "decode", t=1.0)
    lc2.phase(2, "decode", t=2.0)
    assert len(lc2.record(2).spans) == 2


def test_interrupt_closes_span_without_terminal_status():
    lc = LifecycleTracker(TraceBuffer(), MetricsRegistry())
    lc.enqueued(3, t=0.0)
    lc.phase(3, "decode", t=1.0)
    lc.interrupt(3, t=2.0)
    rec = lc.record(3)
    assert not rec.terminal and rec.interrupted
    assert rec.spans[-1].closed and rec.spans[-1].interrupted
    assert lc.open_spans() == []
    # work resumes: a fresh span opens, and finishing completes the chain
    lc.phase(3, "decode", t=3.0)
    lc.finish(3, "done", n_tokens=2, t=4.0)
    assert lc.record(3).complete_chain()


def test_phases_vocabulary_enforced():
    lc = LifecycleTracker()
    lc.enqueued(1)
    with pytest.raises(AssertionError):
        lc.phase(1, "warp_drive")
    assert set(PHASES) == {"queued", "prefill", "ingest", "decode",
                           "spilled"}


def test_ingest_ends_at_first_token():
    """With a prompt tail: queued, prefill, ingest, then decode from the
    first token on; without one, no ingest."""
    lc = LifecycleTracker(TraceBuffer(), MetricsRegistry())
    lc.enqueued(1, t=0.0)
    lc.phase(1, "prefill", t=1.0)
    lc.phase(1, "ingest", t=2.0)
    lc.first_token(1, t=5.0)
    lc.finish(1, "done", n_tokens=3, t=6.0)
    rec = lc.record(1)
    assert rec.phase_sequence() == ["queued", "prefill", "ingest", "decode"]
    assert [s.duration for s in rec.spans] == [1.0, 1.0, 3.0, 1.0]
    assert rec.ttft_s == 5.0 and rec.complete_chain()
    lc.enqueued(2, t=0.0)
    lc.phase(2, "prefill", t=1.0)
    lc.first_token(2, t=1.5)
    lc.phase(2, "decode", t=1.5)
    lc.finish(2, "done", n_tokens=1, t=2.0)
    assert lc.record(2).phase_sequence() == ["queued", "prefill", "decode"]


def test_interrupt_and_reopen_inside_ingest():
    lc = LifecycleTracker(TraceBuffer(), MetricsRegistry())
    lc.enqueued(4, t=0.0)
    lc.phase(4, "prefill", t=1.0)
    lc.phase(4, "ingest", t=2.0)
    lc.interrupt(4, t=3.0)
    assert lc.open_spans() == []
    lc.reopen(4, t=4.0)
    assert lc.record(4).open_span.phase == "ingest"
    lc.first_token(4, t=6.0)
    lc.finish(4, "done", n_tokens=2, t=7.0)
    rec = lc.record(4)
    assert rec.phase_sequence() == ["queued", "prefill", "ingest", "ingest",
                                    "decode"]
    assert rec.spans[2].interrupted and rec.complete_chain()
    assert sum(s.duration for s in rec.spans
               if s.phase == "ingest") == 3.0


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_writes_ring_event_and_profiler_annotation(monkeypatch):
    import repro.obs.trace as T
    made = []

    class FakeAnnotation:
        def __init__(self, name, **args):
            self.name, self.args, self.late, self.open = name, args, {}, False
            made.append(self)

        def set_metadata(self, **args):
            self.late.update(args)

        def __enter__(self):
            self.open = True
            return self

        def __exit__(self, *exc):
            self.open = False

    monkeypatch.setattr(T, "TraceAnnotation", FakeAnnotation)
    obs = Observability()
    with obs.span("serve.step", cat="step", step=3) as st:
        assert made[0].open and made[0].name == "serve.step"
        with obs.span("serve.prefill", rid=7, tokens=64):
            assert made[1].open
        st.set(rows=20, compiled=False)
    assert not any(a.open for a in made)
    assert made[0].args == {"step": 3}
    assert made[0].late == {"rows": 20, "compiled": False}
    assert made[1].args == {"rid": 7, "tokens": 64}
    evs = [e for e in obs.tracer.events() if e["ph"] == "X"]
    assert [e["name"] for e in evs] == ["serve.prefill", "serve.step"]
    inner, outer = evs
    assert inner["args"] == {"rid": 7, "tokens": 64,
                             "parent": "serve.step"}
    assert outer["cat"] == "step" and "parent" not in outer["args"]
    assert outer["args"] == {"step": 3, "rows": 20, "compiled": False}
    assert outer["ts"] <= inner["ts"] and \
        inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    obj = obs.tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    assert {"steps", "phases"} <= trace_features(obj)
    # an exception inside a span still closes it
    with pytest.raises(ValueError):
        with obs.span("serve.commit"):
            raise ValueError("x")
    assert obs.tracer._open == []
    assert obs.tracer.events()[-1]["name"] == "serve.commit"


# ---------------------------------------------------------------------------
# recompile watcher
# ---------------------------------------------------------------------------

def test_recompile_watcher_detects_shape_change():
    obs = Observability()
    fn = obs.wrap_jit(jax.jit(lambda x: x * 2), "f")
    fn(np.ones((4,), np.float32))
    assert fn.n_compiles == 1
    assert obs.recompiles.n_events == 1
    assert obs.recompiles.events[0].is_warmup
    fn(np.ones((4,), np.float32))          # cache hit: no new event
    assert obs.recompiles.n_events == 1
    fn(np.ones((8,), np.float32))          # fresh abstract shape
    assert fn.n_compiles == 2
    ev = obs.recompiles.events[-1]
    assert not ev.is_warmup
    assert any("(4,)" in c and "(8,)" in c for c in ev.changed)
    assert obs.recompiles.n_recompiles == 1
    assert obs.recompiles.counts() == {"f": 2}
    # the trace carries the signature (the CI --require recompile_signature)
    obj = obs.tracer.to_chrome()
    assert "recompile_signature" in trace_features(obj)
    # metrics mirror
    assert obs.metrics.value("recompiles_total", fn="f") == 2.0


def test_watched_function_is_transparent():
    obs = Observability()
    jitted = jax.jit(lambda x: x + 1)
    fn = obs.wrap_jit(jitted, "g")
    out = fn(jnp_ones := np.ones((2,), np.float32))
    np.testing.assert_allclose(np.asarray(out), jnp_ones + 1)
    # attribute passthrough keeps the retrace-pin idiom working
    assert fn._cache_size() == 1


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_fp32():
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(scope="module", params=("llama3.2-1b", "mamba2-2.7b"))
def arch_fp32(request):
    cfg = get_smoke_config(request.param).with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _mk(params, cfg):
    return Engine(params, cfg, ServeConfig(batch=2, n_pages=9, n_slabs=5))


def test_engine_trace_valid_and_chains_complete(arch_fp32):
    params, cfg = arch_fp32
    eng = _mk(params, cfg)
    rng = np.random.default_rng(0)
    hs = [eng.submit(rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                     max_new_tokens=3) for _ in range(3)]
    eng.run()
    obj = eng.obs.tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    feats = trace_features(obj)
    assert {"steps", "spans", "recompile", "phases"} <= feats
    # every terminal request has a complete queued->terminal chain
    recs = eng.obs.lifecycle.terminal_records()
    assert len(recs) == 3
    for r in recs:
        assert r.complete_chain()
        assert r.phase_sequence()[0] == "queued"
    assert eng.obs.lifecycle.open_spans() == []
    # per-request record is reachable through the facade
    rec = eng.lifecycle(hs[0])
    assert rec is not None and rec.ttft_s > 0


def test_stats_is_registry_view(tiny_fp32):
    params, cfg = tiny_fp32
    eng = _mk(params, cfg)
    rng = np.random.default_rng(1)
    eng.submit(rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
               max_new_tokens=4)
    eng.run()
    st = eng.stats()
    m = eng.obs.metrics
    assert st["tokens"] == m.value("tokens_total")
    assert st["requests_done"] == m.value("requests_total", status="done")
    assert st["prefill_tokens"] == m.value("prefill_tokens_total")
    assert st["compile_steps"] + \
        m.histogram("step_s", compile="false").count \
        == m.family_count("step_s")
    assert st["recompiles"] >= 1.0
    # compile-tagged steps are excluded from the nocompile percentile
    assert st["p99_step_nocompile_s"] <= st["p99_step_s"]


def test_run_max_steps_interrupts_spans(tiny_fp32):
    """The run(max_steps) bugfix: surfaced still-active requests get their
    open span closed with an explicit interrupted marker -- the exported
    trace has no dangling async spans."""
    params, cfg = tiny_fp32
    eng = _mk(params, cfg)
    rng = np.random.default_rng(2)
    hs = [eng.submit(rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                     max_new_tokens=64) for _ in range(2)]
    out = eng.run(max_steps=2)
    live = [r for r in out if r.status not in ("done", "aborted",
                                               "truncated")]
    assert live, "workload must still be active at max_steps"
    assert eng.obs.lifecycle.open_spans() == []
    for r in live:
        rec = eng.obs.lifecycle.record(r.rid)
        assert rec.interrupted and rec.spans[-1].interrupted
    assert validate_chrome_trace(eng.obs.tracer.to_chrome()) == []
    # resuming reopens a span in the interrupted phase; chains complete
    eng.run()
    for h in hs:
        rec = eng.obs.lifecycle.record(h.rid)
        assert rec.complete_chain()
    for r in live:
        seq = eng.obs.lifecycle.record(r.rid).phase_sequence()
        assert seq.count("decode") >= 2


def test_prometheus_endpoint_smoke(tiny_fp32):
    params, cfg = tiny_fp32
    eng = _mk(params, cfg)
    rng = np.random.default_rng(3)
    eng.submit(rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
               max_new_tokens=2)
    eng.run()
    text = eng.prometheus_text()
    assert "# TYPE requests_total counter" in text
    assert "# TYPE step_s summary" in text
    assert "pages_alloc_total" in text


# ---------------------------------------------------------------------------
# the paged engine's step spans and ingest phase
# ---------------------------------------------------------------------------

#: every span of a paged engine step
SERVE_SPANS = {"serve.step", "serve.admit", "serve.prefill",
               "serve.headroom", "serve.prefetch", "serve.prepare",
               "serve.dispatch", "serve.sync", "serve.account",
               "serve.commit"}


def _bucketed(params, cfg):
    """A paged engine that prefills 8 tokens of a prompt and streams the
    rest through the decode batch."""
    return Engine(params, cfg, ServeConfig(
        backend="paged", batch=2, n_pages=9, n_slabs=5, prefill_chunk=16,
        prefill_buckets=(8,)))


def _prompts(cfg, lengths, seed=4):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


def test_ttft_is_queued_prefill_ingest(tiny_fp32):
    """For every request finished and never spilled, the queued, prefill
    and ingest spans add up to its TTFT; a request whose prompt the
    prefill takes whole has no ingest span."""
    params, cfg = tiny_fp32
    eng = _bucketed(params, cfg)
    hs = [eng.submit(p, max_new_tokens=3)
          for p in _prompts(cfg, [13, 8, 11, 5])]
    eng.run()
    for h in hs:
        rec = eng.lifecycle(h)
        seq = rec.phase_sequence()
        assert rec.complete_chain() and "spilled" not in seq
        assert ("ingest" in seq) == (len(h.request.prompt) > 8)
        front = sum(s.duration for s in rec.spans
                    if s.phase in ("queued", "prefill", "ingest"))
        assert front == pytest.approx(rec.ttft_s, abs=1e-3)
    # every step's tail_rows counts the rows that fed a prompt token
    steps = [e for e in eng.obs.tracer.events()
             if e["ph"] == "X" and e["name"] == "serve.step"]
    assert sum(e["args"]["tail_rows"] for e in steps) == (13 - 8) + (11 - 8)
    assert sum(e["args"]["prefill_tokens"] for e in steps) == 8 + 8 + 8 + 5
    assert all(e["args"]["tail_rows"] <= e["args"]["rows"] for e in steps)


def test_spill_during_ingest_resumes_into_ingest(tiny_fp32):
    params, cfg = tiny_fp32
    eng = _bucketed(params, cfg)
    h, = [eng.submit(p, max_new_tokens=2) for p in _prompts(cfg, [14])]
    eng.step()                              # prefill 8, stream one token
    core = eng.engine
    assert eng.lifecycle(h).open_span.phase == "ingest"
    core._preempt(h.rid)
    eng.run()
    assert eng.lifecycle(h).phase_sequence() == [
        "queued", "prefill", "ingest", "spilled", "ingest", "decode"]
    assert h.status == "done"


def _profile_events(trace_dir):
    """(plane, name, t0, t1) of every event of the newest xplane."""
    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    return [(plane.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes for line in plane.lines
            for ev in line.events]


def test_spans_reach_the_profiler_trace(tiny_fp32, tmp_path):
    """A real jax.profiler session over a paged run: every serve.* span on
    the host plane, nested in a serve.step; no per-step bank counter; the
    simulated-PIM bank report still computes on demand."""
    params, cfg = tiny_fp32
    eng = _bucketed(params, cfg)
    eng.submit(_prompts(cfg, [11])[0], max_new_tokens=2)
    eng.run()                               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in _prompts(cfg, [13, 6], seed=5):
            eng.submit(p, max_new_tokens=3)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    evs = [e for e in _profile_events(tmp_path)
           if e[1].startswith("serve.")]
    assert {name for _, name, _, _ in evs} == SERVE_SPANS
    assert {plane for plane, _, _, _ in evs} == {"/host:CPU"}
    steps = [(a, b) for _, name, a, b in evs if name == "serve.step"]
    for _, name, a, b in evs:
        assert any(s0 <= a and b <= s1 for s0, s1 in steps), name
    ring = eng.obs.tracer.events()
    assert not any(e["ph"] == "C" and "bank" in e["name"] for e in ring)
    assert {e["name"] for e in ring if e["ph"] == "X"} == SERVE_SPANS
    # the bank report computes the traffic of the requests now active
    assert eng.engine.bank_report()["t_real_s"] == 0.0
    eng.submit(_prompts(cfg, [9])[0], max_new_tokens=3)
    eng.step()
    rep = eng.engine.bank_report()
    assert rep["t_real_s"] > 0 and "imbalance" in rep


@pytest.mark.parametrize("arch", ["zamba2-2.7b", "mamba2-2.7b"])
def test_step_spans_count_kv_tokens_and_pages(arch):
    """``serve.step`` carries the K/V positions one attention layer read
    (each row's length with the appended token) and the pages in use; a
    model without K/V reads 0 for both."""
    cfg = get_smoke_config(arch).with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, ServeConfig(backend="paged", batch=2,
                                          n_pages=9, n_slabs=5))
    eng.submit(_prompts(cfg, [20])[0], max_new_tokens=4)
    eng.run()
    steps = [e["args"] for e in eng.obs.tracer.events()
             if e["ph"] == "X" and e["name"] == "serve.step"
             and e["args"]["rows"]]
    kv = arch == "zamba2-2.7b"
    assert [s["kv_tokens"] for s in steps] == \
        ([21, 22, 23] if kv else [0, 0, 0])
    assert [s["kv_pages"] for s in steps] == [int(kv)] * 3
