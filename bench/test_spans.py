"""The readers of the program's ``serve.*`` spans (``bench/spans.py`` and
the four metrics built on it) on a small recorded event list and ring, and
what they read from a program without those spans: nothing."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

from bench import smoke, spans, yardstick
from repro.obs import LifecycleTracker, TraceBuffer

HERE = os.path.dirname(os.path.abspath(__file__))
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _reader(name):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _op(t0, t1):
    return {"plane": DEV, "line": "XLA Ops", "name": "fusion", "t0": t0,
            "t1": t1}


def _host(name, t0, t1):
    return {"plane": HOST, "line": "python", "name": name, "t0": t0,
            "t1": t1}


#: two engine steps on the profiler's clock, in seconds.  Device idle:
#: [0.100, 0.101] under serve.account, [0.1012, 0.104] under serve.prefill
#: (inside serve.admit inside serve.step), [0.200, 0.206] in the second
#: step's own time, [0.208, 0.210] after it.
EVENTS = [
    _op(0.000, 0.100), _op(0.1010, 0.1012), _op(0.104, 0.200),
    _op(0.206, 0.208),
    _host("bench.window", 0.0, 0.210),
    _host("serve.step", 0.000, 0.1020),
    _host("serve.dispatch", 0.000, 0.001),
    _host("serve.sync", 0.001, 0.1002),
    _host("serve.account", 0.1002, 0.1008),
    _host("serve.commit", 0.1008, 0.1015),
    _host("serve.step", 0.1022, 0.2060),
    _host("serve.admit", 0.1022, 0.1100),
    _host("serve.prefill", 0.1023, 0.1090),
    _host("serve.sync", 0.1100, 0.2000),
]
WINDOW = (0.0, 0.210)


def test_idle_by_span_names_the_innermost_span():
    got = spans.idle_by_span(EVENTS, WINDOW)
    assert got == {"serve.account": pytest.approx(0.001, abs=1e-12),
                   "serve.prefill": pytest.approx(0.0028, abs=1e-12),
                   "serve.step": pytest.approx(0.006, abs=1e-12),
                   "outside": pytest.approx(0.002, abs=1e-12)}
    # the whole idle time, and nothing when no device plane was traced
    assert sum(got.values()) == pytest.approx(0.0118, abs=1e-12)
    assert spans.idle_by_span([e for e in EVENTS if e["plane"] == HOST],
                              WINDOW) == {}


def test_idle_in_counts_the_gaps_of_an_interval():
    busy, = spans.busy_intervals(EVENTS, WINDOW)
    assert spans.idle_in(busy, 0.0, 0.1020) == pytest.approx(0.0018)
    assert spans.idle_in(busy, 0.1022, 0.2060) == pytest.approx(0.0078)
    assert spans.idle_in(busy, 0.05, 0.06) == 0.0


def _ctx(events=EVENTS, steps=(), records=(), window=(1.0, 2.0), buf=None):
    """A reader's context: the profiler events above, and a ring holding
    ``steps`` ((start, seconds, rows, tail_rows)) and ``records`` ((due,
    lifecycle record)), times in seconds from the ring's zero."""
    buf = buf or TraceBuffer()
    zero = -buf.ts_of(0.0) * 1e-6
    for t, d, rows, tail in steps:
        buf.complete("serve.step", "step", ts=buf.ts_of(zero + t),
                     dur=d * 1e6, rows=rows, tail_rows=tail)
    recs = {i: rec for i, (_, rec) in enumerate(records)}
    client = SimpleNamespace(records=[
        SimpleNamespace(due=zero + due, handle=i)
        for i, (due, _) in enumerate(records)])
    engine = SimpleNamespace(obs=SimpleNamespace(tracer=buf),
                             lifecycle=recs.get)
    return SimpleNamespace(
        events=events, trace_window=WINDOW, client=client, engine=engine,
        window=(zero + window[0], zero + window[1]), yardstick=yardstick)


def test_step_readers_on_a_ring():
    steps = [(0.5, 0.9, 20, 20)]                      # before the window
    steps += [(1.05 + 0.04 * i, 0.15 + 0.001 * i, 20, i % 3)
              for i in range(21)]
    steps += [(2.05, 0.9, 20, 20)]                    # after it
    ctx = _ctx(steps=steps)
    durs = [0.15 + 0.001 * i for i in range(21)]
    assert _reader("step_p95_ms.chat")(ctx) == pytest.approx(
        1e3 * yardstick.percentile(durs, 95))
    tails = sum(i % 3 for i in range(21))
    assert _reader("tail_row_share.chat")(ctx) == pytest.approx(
        100.0 * tails / (21 * 20))
    # mean device idle inside the two traced serve.step spans
    assert _reader("step_idle_ms.chat")(ctx) == pytest.approx(
        1e3 * (0.0018 + 0.0078) / 2)


def test_ingest_p90_reads_the_ingest_phase():
    lc, buf = LifecycleTracker(), TraceBuffer()
    zero = -buf.ts_of(0.0) * 1e-6
    records = []
    for i in range(10):                 # ingest 0.08 .. 0.8 s, due in window
        t = zero + 1.05 + 0.01 * i
        lc.enqueued(i, t=t)
        lc.phase(i, "prefill", t=t + 0.01)
        lc.phase(i, "ingest", t=t + 0.02)
        lc.first_token(i, t=t + 0.02 + 0.08 * (i + 1))
        records.append((1.05 + 0.01 * i, lc.record(i)))
    lc.enqueued(10, t=zero + 1.2)       # no tail: 0
    lc.phase(10, "prefill", t=zero + 1.21)
    lc.first_token(10, t=zero + 1.22)
    lc.phase(10, "decode", t=zero + 1.22)
    records.append((1.2, lc.record(10)))
    lc.enqueued(11, t=zero + 1.5)       # still ingesting: to the window end
    lc.phase(11, "prefill", t=zero + 1.51)
    lc.phase(11, "ingest", t=zero + 1.52)
    records.append((1.5, lc.record(11)))
    records.append((0.5, lc.record(0)))  # due before the window: left out
    ctx = _ctx(records=records, buf=buf)
    want = [0.08 * (i + 1) for i in range(10)] + [0.0, 2.0 - 1.52]
    assert _reader("ingest_p90_s.chat")(ctx) == pytest.approx(
        yardstick.percentile(want, 90))


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    ctx = _ctx(events=[e for e in EVENTS
                       if not e["name"].startswith("serve.")])
    for name in ("step_p95_ms.chat", "tail_row_share.chat",
                 "step_idle_ms.chat"):
        assert _reader(name)(ctx) is None, name
    import repro.obs
    monkeypatch.setattr(repro.obs, "PHASES",
                        ("queued", "prefill", "decode", "spilled"))
    lc = LifecycleTracker()
    lc.enqueued(0, t=0.0)
    assert _reader("ingest_p90_s.chat")(_ctx(records=[(1.0, lc.record(0))])
                                        ) is None


def test_traced_smoke_run_reads_the_program_spans():
    """At smoke size on the CPU the three readers of the program's own
    spans read a number; the device-trace one finds no device plane."""
    out = smoke.run("mamba2-chat-open", seed=2 ** 31 + 5, trace=True)
    got = out["metrics"]
    assert {"ingest_p90_s.chat", "step_p95_ms.chat",
            "tail_row_share.chat"} <= set(got)
    assert "step_idle_ms.chat" not in got
    assert 0.0 < got["tail_row_share.chat"]["value"] < 100.0
    assert got["step_p95_ms.chat"]["value"] > 0.0
    assert out["correct"] is True
