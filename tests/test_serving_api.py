"""Request-lifecycle serving API: ServeConfig, streaming handles, open-loop
step(), abort at every lifecycle point, truncation, stats schema."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.serving.api import Engine, RequestHandle, ServeConfig
from repro.serving.engine import PagedServingEngine


@pytest.fixture(scope="module")
def tiny_fp32():
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


#: attention alone, and the benchmark's state-space family
ARCHS = ("llama3.2-1b", "mamba2-2.7b")


@pytest.fixture(scope="module", params=ARCHS)
def arch_fp32(request):
    cfg = get_smoke_config(request.param).with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _mk(params, cfg, **kw):
    base = dict(batch=2, n_pages=9, n_slabs=5)
    base.update(kw)
    return Engine(params, cfg, ServeConfig(**base))


# ---------------------------------------------------------------------------
# config + construction
# ---------------------------------------------------------------------------

def test_serve_config_selects_backend(tiny_fp32):
    params, cfg = tiny_fp32
    eng = Engine(params, cfg, ServeConfig(batch=3)).engine
    assert isinstance(eng, PagedServingEngine)
    assert len(eng.rows) == 3
    assert eng.pool.n_slabs == 7                 # 2*batch + 1 default


def test_serve_config_accepts_only_paged():
    for backend in ("slots", "gpu"):
        with pytest.raises(ValueError, match="only serving engine"):
            ServeConfig(backend=backend)


# ---------------------------------------------------------------------------
# streaming: tokens surface per step, handle iteration drives the loop
# ---------------------------------------------------------------------------

def test_streaming_order_matches_final_output(arch_fp32):
    params, cfg = arch_fp32
    eng = _mk(params, cfg)
    rng = np.random.default_rng(0)
    hs = [eng.submit(rng.integers(0, cfg.vocab_size, 8 + 3 * i
                                  ).astype(np.int32), max_new_tokens=5)
          for i in range(3)]
    streamed = {h.rid: [] for h in hs}
    arrivals = 0
    while eng.step():
        for h in hs:
            got = h.new_tokens()
            streamed[h.rid].extend(got)
            arrivals += bool(got)
    for h in hs:
        streamed[h.rid].extend(h.new_tokens())
        assert h.status == "done"
        assert streamed[h.rid] == h.output, (h.rid, streamed[h.rid], h.output)
        assert len(h.output) == 5
    assert arrivals > 3          # tokens arrived incrementally, not at drain


def test_handle_iteration_drives_engine(tiny_fp32):
    params, cfg = tiny_fp32
    eng = _mk(params, cfg)
    rng = np.random.default_rng(1)
    h1 = eng.submit(rng.integers(0, cfg.vocab_size, 10).astype(np.int32),
                    max_new_tokens=6)
    h2 = eng.submit(rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                    max_new_tokens=4)
    toks = list(h1)              # continuous batching: h2 progresses too
    assert toks == h1.output and len(toks) == 6
    assert h1.status == "done"
    h2.result()
    assert h2.status == "done" and len(h2.output) == 4


# ---------------------------------------------------------------------------
# abort: queued, mid-decode, spilled
# ---------------------------------------------------------------------------

def test_abort_mid_decode_frees_capacity(arch_fp32):
    params, cfg = arch_fp32
    eng = _mk(params, cfg)
    rng = np.random.default_rng(2)
    ha = eng.submit(rng.integers(0, cfg.vocab_size, 10).astype(np.int32),
                    max_new_tokens=12)
    hb = eng.submit(rng.integers(0, cfg.vocab_size, 11).astype(np.int32),
                    max_new_tokens=12)
    for _ in range(3):
        eng.step()
    assert ha.status == "running" and len(ha.output) >= 2
    seen = len(ha.output)
    assert ha.abort()
    assert ha.status == "aborted"
    assert not ha.abort()        # terminal: second abort is a no-op
    hb.result()
    assert hb.status == "done" and len(hb.output) == 12
    # freed capacity is immediately reusable
    hc = eng.submit(rng.integers(0, cfg.vocab_size, 9).astype(np.int32),
                    max_new_tokens=3)
    hc.result()
    assert hc.status == "done" and len(hc.output) == 3
    # the aborted handle kept its streamed tokens, and no more arrived
    assert len(ha.output) == seen
    pool = eng.engine.pool
    assert pool.free_pages == pool.usable_pages
    assert len(pool.page_table) == 0
    st = eng.stats()
    assert st["requests_aborted"] == 1 and st["requests_done"] == 2


def test_abort_queued_request_never_runs(arch_fp32):
    params, cfg = arch_fp32
    eng = _mk(params, cfg, batch=1)
    rng = np.random.default_rng(3)
    h1 = eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4)
    h2 = eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4)
    assert h2.status == "queued"
    assert h2.abort() and h2.status == "aborted"
    done = eng.run()
    assert h1.status == "done"
    assert h2.output == []
    assert {r.rid for r in done} == {h1.rid, h2.rid}
    assert len(eng.engine.sched) == 0


def test_abort_spilled_request_drops_pages(tiny_fp32):
    """Preempt a victim into host spill, then abort it: the blob and its
    page references must be dropped, the survivor must finish normally."""
    params, cfg = tiny_fp32
    eng = _mk(params, cfg, batch=2, n_pages=4, n_slabs=5)
    rng = np.random.default_rng(4)
    hs = [eng.submit(rng.integers(0, cfg.vocab_size, 120).astype(np.int32),
                     max_new_tokens=12) for _ in range(2)]
    while not eng.engine.spilled and eng.step():
        pass
    assert eng.engine.spilled, "pool too large: no preemption happened"
    victim_rid = next(iter(eng.engine.spilled))
    victim = next(h for h in hs if h.rid == victim_rid)
    survivor = next(h for h in hs if h.rid != victim_rid)
    assert victim.abort()
    assert victim.status == "aborted"
    assert victim_rid not in eng.engine.spilled
    assert len(eng.engine.sched) == 0     # heap entry tombstoned + pruned
    survivor.result()
    assert survivor.status == "done" and len(survivor.output) == 12
    pool = eng.engine.pool
    assert pool.free_pages == pool.usable_pages
    assert pool.free_slabs == pool.n_slabs - 1


# ---------------------------------------------------------------------------
# run(max_steps) + stats schema (the {"tokens": 0} bugfix)
# ---------------------------------------------------------------------------

def test_run_step_cap_surfaces_active_requests(arch_fp32):
    params, cfg = arch_fp32
    eng = _mk(params, cfg, batch=1)
    rng = np.random.default_rng(5)
    h1 = eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=10)
    h2 = eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=10)
    out = eng.run(max_steps=2)
    statuses = {r.rid: r.status for r in out}
    assert statuses[h1.rid] == "running"     # surfaced, not dropped
    assert statuses[h2.rid] == "queued"
    st = eng.stats()
    assert st["active_requests"] == 1 and st["queued_requests"] == 1
    # drain completes normally afterwards
    done = eng.run()
    assert all(r.status == "done" for r in done)


def test_paged_engine_truncates_when_pages_run_out(tiny_fp32):
    """A request stopped because the pool has no page left for its next
    token (not by max_new/eos) was clipped: it must end `truncated`."""
    params, cfg = tiny_fp32
    eng = _mk(params, cfg, batch=1, n_pages=2)
    rng = np.random.default_rng(6)
    h = eng.submit(rng.integers(0, cfg.vocab_size, 120).astype(np.int32),
                   max_new_tokens=50)
    h.result()
    assert h.status == "truncated"
    assert h.request.truncated
    assert 0 < len(h.output) < 50
    assert eng.stats()["requests_truncated"] == 1


_SCHEMA = ("tokens", "wall_s", "tokens_per_s", "prefill_tokens",
           "requests_done", "requests_aborted", "requests_truncated",
           "active_requests", "queued_requests",
           "mean_ttft_s", "p50_ttft_s", "p99_ttft_s",
           "p50_step_s", "p99_step_s",
           "p50_tok_latency_s", "p99_tok_latency_s")


def test_stats_full_schema_before_any_finish(arch_fp32):
    params, cfg = arch_fp32
    eng = _mk(params, cfg)
    st = eng.stats()
    for key in _SCHEMA:
        assert key in st, key
        assert st[key] == 0.0, (key, st[key])
    for key in ("preemptions", "occupancy", "fragmentation",
                "gather_bytes", "pages_allocated", "shared_page_hits",
                "shared_page_savings"):
        assert key in st and st[key] == 0.0, key
