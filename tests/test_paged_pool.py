"""Paged state/KV pool: allocation invariants, preemption round-trip,
time-axis recapacity regression, scheduler-driven serving, sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import CELL, cell_state_config, greedy_reference

from repro.configs import get_smoke_config
from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.core import pimsim
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.serving.api import ServeConfig
from repro.serving.engine import PagedServingEngine, Request
from repro.serving.memory import (PAGE_TOKENS, BankAwarePlacement,
                                  BankTopology, PagedStatePool, pages_for)
from repro.serving.sampler import SamplingConfig, sample
from repro.serving.scheduler import Scheduler, SchedulerConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("llama3.2-1b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(scope="module")
def tiny_fp32():
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_placement_alloc_free_invariants():
    topo = BankTopology(pseudo_channels=4, bank_pairs=4)
    pl = BankAwarePlacement(33, topo)
    assert pl.n_free == 32                       # page 0 reserved
    a = pl.alloc(8)
    assert a is not None and len(set(a)) == 8 and 0 not in a
    # bank-aware: 8 pages over 16 coords -> no coordinate holds two
    assert pl.live_map().max() == 1
    b = pl.alloc(24)
    assert pl.n_free == 0
    assert pl.alloc(1) is None                   # exhausted, state unchanged
    assert pl.n_free == 0
    pl.free(a)
    assert pl.n_free == 8
    c = pl.alloc(8)
    assert set(c) == set(a)                      # ids conserved, no leaks
    pl.free(b)
    pl.free(c)
    assert pl.n_free == 32
    assert pl.live_map().sum() == 0


def test_pool_register_grow_release(tiny):
    params, cfg = tiny
    pool = PagedStatePool(cfg, n_pages=9, n_slabs=5)
    assert pool.usable_pages == 8
    assert pool.register(1, 2) and pool.register(2, 3)
    assert pool.free_pages == 3
    assert pool.grow(1, 3)
    assert pool.free_pages == 0
    assert not pool.grow(2, 1)                   # full, copy-free failure
    # fragmentation: rid1 holds 5 pages / 300 tokens, rid2 3 pages / 384
    frag = pool.fragmentation({1: 300, 2: 384})
    assert frag == pytest.approx(1.0 - 684 / (8 * PAGE_TOKENS))
    assert pool.occupancy() == 1.0
    pool.release(1)
    assert pool.free_pages == 5 and pool.free_slabs == 3
    pool.release(2)
    assert pool.free_pages == 8 and pool.free_slabs == 4


def test_pimsim_scores_real_page_map():
    sys_cfg = pimsim.SystemConfig()
    uniform = np.full((4, 4), 10.0)
    hot = np.zeros((4, 4))
    hot[0, 0] = 160.0                            # same traffic, one bank pair
    r_u = pimsim.placement_step_latency(uniform, sys_cfg)
    r_h = pimsim.placement_step_latency(hot, sys_cfg)
    assert r_u["conflict_factor"] == pytest.approx(1.0)
    assert r_h["conflict_factor"] > 3.0
    assert r_h["t_real_s"] > r_u["t_real_s"]


# ---------------------------------------------------------------------------
# time-axis recapacity regression (what _recapacity used to guess)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["mx8", "fp16"])
def test_recapacity_stacked_batch_divisible_by_128(fmt):
    """B=128 stacked caches: the retired heuristic picked the first axis
    divisible by 128 -- the *batch* axis on (G, B, T, ...) leaves -- and
    would have resized batch instead of time.  Pin the explicit behavior."""
    sq = StateQuantConfig(fmt=fmt, rounding="nearest", backend="jnp")
    cache = AC.init_kv_cache(128, 256, 1, 16, sq)
    k = jax.random.normal(jax.random.PRNGKey(0), (128, 256, 1, 16))
    cache = AC.KVCache(
        F.quantize(k, "mx8") if fmt == "mx8" else k.astype(jnp.float16),
        cache.v, jnp.full((128,), 256, jnp.int32), cache.fmt,
        cache.v_width, cache.time_axis)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (2,) + x.shape), cache)
    assert stacked.stack_offset == 1             # lengths (2, 128)

    grown = AC.recapacity(stacked, 384)
    leaf = (grown.k.payload["mantissa"] if fmt == "mx8" else grown.k)
    assert leaf.shape[:3] == (2, 128, 384)       # time grew, batch intact
    if fmt == "mx8":
        assert grown.k.shape == (128, 384, 1, 16)  # logical aux follows
        np.testing.assert_array_equal(
            grown.k.payload["mantissa"][:, :, :256],
            stacked.k.payload["mantissa"])

    trimmed = AC.recapacity(stacked, 128)
    leaf_t = (trimmed.k.payload["mantissa"] if fmt == "mx8" else trimmed.k)
    assert leaf_t.shape[:3] == (2, 128, 128)
    src = (stacked.k.payload["mantissa"] if fmt == "mx8" else stacked.k)
    np.testing.assert_array_equal(np.asarray(leaf_t),
                                  np.asarray(src[:, :, :128]))


def test_kvcache_max_len_uses_time_axis():
    sq = StateQuantConfig(fmt="mx8", rounding="nearest", backend="jnp")
    cache = AC.init_kv_cache(2, 256, 1, 16, sq)
    assert cache.time_axis == 1
    assert cache.max_len == 256


# ---------------------------------------------------------------------------
# preemption round-trip: evict -> resume -> bit-identical logits
# ---------------------------------------------------------------------------

def test_preemption_roundtrip_bit_identical_logits(tiny):
    params, cfg = tiny                           # mx8 + stochastic rounding
    pool = PagedStatePool(cfg, n_pages=9, n_slabs=5)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    pr = jnp.asarray(prompt)[None]
    logits, row = jax.jit(lambda p, b: M.prefill(p, cfg, b))(
        params, {"tokens": pr, "targets": pr})
    assert pool.register(7, pages_for(len(prompt)))
    pool.insert_prefill(7, row)
    tok = int(jnp.argmax(logits[0]))
    lengths = np.array([12, 0], np.int32)
    for step in (1, 2):                          # warm the caches a little
        lg = pool.decode(params, [7, None],
                         np.array([tok, 0], np.int32), lengths, seed=step)
        tok = int(jnp.argmax(lg[0]))
        lengths[0] += 1

    # host-copy snapshot: the decode step *donates* the pools (in-place
    # page/slab updates), so device-side references would be deleted
    snapshot = [np.asarray(x) for x in pool.pools]
    pages_before = list(pool.page_table[7])
    lg_a = np.asarray(pool.decode(params, [7, None],
                                  np.array([tok, 0], np.int32),
                                  lengths, seed=42))
    pool.pools = [jnp.asarray(x) for x in snapshot]  # rewind the step

    sp = pool.spill(7, int(lengths[0]))          # evict to host
    assert 7 not in pool.page_table
    assert pool.resume(7, sp)                    # re-pin (fresh placement)
    lg_b = np.asarray(pool.decode(params, [7, None],
                                  np.array([tok, 0], np.int32),
                                  lengths, seed=42))
    np.testing.assert_array_equal(lg_a[0], lg_b[0])
    # placement may differ; identity must not depend on physical page ids
    assert len(pool.page_table[7]) == len(pages_before)


@pytest.mark.parametrize("arch", ["mamba2-2.7b" + CELL,
                                  "zamba2-2.7b" + CELL])
def test_state_slab_round_trips_bit_identical(arch):
    """The recurrent state lives in the pool in the state-update kernel's
    layout: a prefilled row reads back as it went in, a spilled and resumed
    request and a fork's copied slab decode as the original does."""
    cfg = cell_state_config(arch[:-len(CELL)])
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    pool = PagedStatePool(cfg, n_pages=9, n_slabs=5)
    n = PAGE_TOKENS
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, n)
    pr = jnp.asarray(prompt, jnp.int32)[None]
    logits, row = jax.jit(lambda p, b: M.prefill(p, cfg, b))(
        params, {"tokens": pr, "targets": pr})
    assert pool.register(1, pages_for(n + 1))
    pool.insert_prefill(1, row)
    # insert_request stores the rows, the gather path reads them back
    dense = pool.paging.gather(
        pool.pools, jnp.asarray([pool.page_table[1]], jnp.int32),
        jnp.asarray([pool.slab_of[1]], jnp.int32),
        jnp.asarray([n], jnp.int32))
    states = [(spec, a, b) for spec, a, b in zip(
        pool.paging.specs, jax.tree.leaves(dense), jax.tree.leaves(row))
        if spec.mx8_field]
    assert len(states) == 3
    for spec, a, b in states:
        np.testing.assert_array_equal(a, b, err_msg=spec.mx8_field)

    tok = np.array([int(jnp.argmax(logits[0])), 0], np.int32)
    lengths = np.array([n, 0], np.int32)

    def step(rid):
        return np.asarray(pool.decode(params, [rid, None], tok, lengths,
                                      seed=5))[0]

    snapshot = [np.array(x) for x in pool.pools]
    want = step(1)
    pool.pools = [jnp.asarray(x) for x in snapshot]
    # a fork at the page boundary copies the slab row alone; the child's
    # first token opens a page of its own
    assert pool.fork(1, 2, n) and pool.grow(2, 1)
    slab_rows = lambda rid: [np.array(p[pool.slab_of[rid]]) for p, spec
                             in zip(pool.pools, pool.paging.specs)
                             if spec.kind == "slab"]
    for a, b in zip(slab_rows(1), slab_rows(2)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(step(2), want)
    pool.release(2)
    pool.pools = [jnp.asarray(x) for x in snapshot]
    # spill to the host and resume into another slab
    sp = pool.spill(1, n)
    assert pool.register(3, 1)                 # take the freed slab
    assert pool.resume(1, sp)
    assert pool.slab_of[1] != 1 or pool.slab_of[3] != 1
    np.testing.assert_array_equal(step(1), want)


# ---------------------------------------------------------------------------
# end-to-end serving through the paged engine
# ---------------------------------------------------------------------------

def _reference_outputs(params, cfg, prompts, n_new):
    return {i: greedy_reference(params, cfg, p, n_new)
            for i, p in enumerate(prompts)}


def test_paged_engine_mixed_workload_matches_greedy(tiny_fp32):
    """Short + long prompts (chunked prefill for the long one) through a
    small pool; every request's greedy tokens must match the dense
    single-request reference."""
    params, cfg = tiny_fp32
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (10, 150, 9, 40)]
    refs = _reference_outputs(params, cfg, prompts, 5)

    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=3, n_pages=7, n_slabs=7, prefill_chunk=128))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    done = eng.run()
    assert len(done) == len(prompts)
    for r in done:
        assert not r.truncated
        assert r.output == refs[r.rid], (r.rid, r.output, refs[r.rid])
    stats = eng.stats()
    assert stats["tokens"] == 5 * len(prompts)
    assert 0.0 <= stats["occupancy"] <= 1.0
    assert 0.0 <= stats["fragmentation"] < 1.0
    assert "p99_ttft_s" in stats and "p50_tok_latency_s" in stats


def test_paged_engine_prefill_buckets(tiny_fp32):
    """Opt-in prefill bucketing (the JH103 lint-finding fix): prefilling
    each prompt whole at the smallest bucket that covers it, the padding
    masked, must not change greedy outputs, while collapsing
    one-prefill-compile-per-prompt-length to one per bucket."""
    params, cfg = tiny_fp32
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 11, 13, 42, 44, 46)]
    refs = _reference_outputs(params, cfg, prompts, 4)

    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=3, n_pages=9, n_slabs=7, prefill_chunk=128,
        prefill_buckets=(8, 32, 128)))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    done = eng.run()
    assert len(done) == len(prompts)
    for r in done:
        assert r.output == refs[r.rid], (r.rid, r.output, refs[r.rid])
    # six distinct prompt lengths, but only two buckets actually prefill
    # (9-13 -> 32, 42-46 -> 128): the compile count follows the bucket set
    assert eng.obs.recompiles.counts().get("engine.prefill", 0) == 2


#: the model families of the chip cells, and attention alone
MASKED_ARCHS = ("llama3.2-1b", "mamba2-2.7b", "zamba2-2.7b")


@pytest.fixture(scope="module", params=MASKED_ARCHS)
def masked_fp32(request):
    cfg = get_smoke_config(request.param).with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _bucketed_engine(params, cfg):
    from repro.serving.api import Engine, ServeConfig
    return Engine(params, cfg, ServeConfig(
        backend="paged", batch=2, n_pages=9, n_slabs=5, prefill_chunk=128,
        prefill_buckets=(16, 32, 64)))


def _serve_steps(eng):
    return [e for e in eng.obs.tracer.events()
            if e["ph"] == "X" and e["name"] == "serve.step"]


def test_paged_engine_pads_covered_prompts(masked_fp32):
    """Every prompt a bucket covers is prefilled whole at its bucket, the
    padding masked: the greedy tokens are the dense reference's, no
    request ingests a tail, no decode row feeds a prompt token, the pad
    counter reads the padding, and each bucket compiles once."""
    params, cfg = masked_fp32
    lengths = (5, 16, 20, 32, 50, 64)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    refs = _reference_outputs(params, cfg, prompts, 4)

    eng = _bucketed_engine(params, cfg)
    hs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run()
    for i, h in enumerate(hs):
        assert h.status == "done"
        assert h.request.output == refs[i], (i, h.request.output, refs[i])
        assert "ingest" not in eng.lifecycle(h).phase_sequence()
    assert all(e["args"]["tail_rows"] == 0 for e in _serve_steps(eng))
    st = eng.stats()
    assert st["prefill_pad_tokens"] == (16 - 5) + (32 - 20) + (64 - 50)
    assert st["prefill_tail_tokens"] == 0
    assert eng.obs.recompiles.counts()["engine.prefill"] == 3
    spans = [e["args"] for e in eng.obs.tracer.events()
             if e["ph"] == "X" and e["name"] == "serve.prefill"]
    assert sorted((a["tokens"], a["padded"], a["tail"]) for a in spans) == [
        (5, 16, 0), (16, 16, 0), (20, 32, 0), (32, 32, 0), (50, 64, 0),
        (64, 64, 0)]


def test_paged_engine_new_prompt_lengths_compile_nothing(masked_fp32):
    """Once each bucket has run, prompts of lengths not seen before pad
    into them without a single compile: no device op sees the (1, n)
    tokens of an unpadded prompt."""
    params, cfg = masked_fp32
    eng = _bucketed_engine(params, cfg)
    rng = np.random.default_rng(9)
    for n in (16, 32, 64):
        eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                   max_new_tokens=2)
        eng.run()
    compiles = []

    def listen(event, duration, **_):
        if "backend_compile" in event:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for n in (5, 23, 41, 63):
            eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                       max_new_tokens=2)
            eng.run()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert eng.stats()["prefill_tail_tokens"] == 0


def test_paged_engine_streams_past_largest_bucket(masked_fp32):
    """A prompt longer than the largest bucket prefills that bucket and
    streams the rest through the decode batch, and still gives the
    dense reference's greedy tokens beside a padded one."""
    params, cfg = masked_fp32
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (80, 40)]
    refs = _reference_outputs(params, cfg, prompts, 4)

    eng = _bucketed_engine(params, cfg)
    hs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run()
    for i, h in enumerate(hs):
        assert h.request.output == refs[i], (i, h.request.output, refs[i])
    assert "ingest" in eng.lifecycle(hs[0]).phase_sequence()
    assert "ingest" not in eng.lifecycle(hs[1]).phase_sequence()
    assert sum(e["args"]["tail_rows"] for e in _serve_steps(eng)) == 80 - 64
    st = eng.stats()
    assert st["prefill_tail_tokens"] == 80 - 64
    assert st["prefill_pad_tokens"] == 64 - 40


def test_paged_engine_growth_preemption_e2e(tiny_fp32):
    """Pool too small for both requests' full contexts: one must be evicted
    when the other's block table grows, then resume and still produce the
    exact greedy continuation."""
    params, cfg = tiny_fp32
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 120).astype(np.int32)
               for _ in range(2)]
    refs = _reference_outputs(params, cfg, prompts, 12)

    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2, n_pages=4, n_slabs=5, prefill_chunk=128))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=12))
    done = eng.run()
    assert len(done) == 2
    assert eng.preemptions >= 1                  # growth forced an eviction
    for r in done:
        assert not r.truncated
        assert r.output == refs[r.rid], (r.rid, r.output, refs[r.rid])


def test_paged_pool_doubles_inflight_in_same_bytes(tiny_fp32):
    """Acceptance: within the byte budget of a slots=4 x cap=256 fixed pool,
    the paged pool keeps 2x as many short requests in flight."""
    params, cfg = tiny_fp32
    slots, cap = 4, 256
    probe = PagedStatePool(cfg, n_pages=2, n_slabs=2)
    budget = slots * ((cap // PAGE_TOKENS) * probe.page_nbytes
                      + probe.slab_nbytes)

    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2 * slots, byte_budget=budget, n_pages=None,
        n_slabs=2 * slots + 1, prefill_chunk=128))
    assert eng.pool.bytes_total() <= budget
    rng = np.random.default_rng(4)
    for i in range(2 * slots):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size, 8 + i
                                               ).astype(np.int32),
                           max_new_tokens=4))
    eng._admit()
    assert len(eng.active) == 2 * slots          # all resident at once
    done = eng.run()
    assert len(done) == 2 * slots
    assert all(len(r.output) == 4 for r in done)


def test_paged_engine_priority_scheduling(tiny_fp32):
    """Lower priority value finishes first when capacity forces queueing."""
    params, cfg = tiny_fp32
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=1, n_pages=3, n_slabs=3,
        scheduler=SchedulerConfig(policy="priority")))
    rng = np.random.default_rng(5)
    for i, prio in enumerate((5, 0, 3)):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size, 8
                                               ).astype(np.int32),
                           max_new_tokens=3, priority=prio))
    done = eng.run()
    assert [r.rid for r in done] == [1, 2, 0]    # by priority, not arrival


# ---------------------------------------------------------------------------
# scheduler unit behavior
# ---------------------------------------------------------------------------

def test_scheduler_policies():
    def mk(rid, prio=0, deadline=None, t=0.0):
        r = Request(rid=rid, prompt=np.zeros(1, np.int32), priority=prio,
                    deadline=deadline)
        r.t_submit = t
        return r

    s = Scheduler(SchedulerConfig(policy="priority"))
    a, b, c = mk(0, prio=2, t=0.0), mk(1, prio=0, t=1.0), mk(2, prio=2, t=2.0)
    for r in (a, b, c):
        s.push(r)
    assert s.pop() is b and s.pop() is a and s.pop() is c

    s = Scheduler(SchedulerConfig(policy="deadline"))
    d, e = mk(0, deadline=9.0, t=0.0), mk(1, deadline=1.0, t=1.0)
    s.push(d)
    s.push(e)
    assert s.pop() is e                          # EDF
    assert s.choose_victim([d, e]) is d          # latest deadline evicted
    assert s.should_preempt(e, d)
    assert not s.should_preempt(d, e)

    s = Scheduler(SchedulerConfig(policy="fcfs"))
    s.push(mk(0, t=1.0))
    assert not s.should_preempt(mk(1, t=2.0), s.peek())


# ---------------------------------------------------------------------------
# sampler: top-p
# ---------------------------------------------------------------------------

def test_top_p_restricts_to_nucleus():
    logits = jnp.log(jnp.array([[0.6, 0.3, 0.08, 0.02]]))
    key = jax.random.PRNGKey(0)
    cfg = SamplingConfig(temperature=1.0, top_p=0.5)
    toks = [int(sample(logits, cfg, jax.random.fold_in(key, i))[0])
            for i in range(32)]
    assert set(toks) == {0}                      # only the top token survives
    cfg = SamplingConfig(temperature=1.0, top_p=0.85)
    toks = [int(sample(logits, cfg, jax.random.fold_in(key, i))[0])
            for i in range(64)]
    assert set(toks) <= {0, 1} and 1 in toks
    # top_p=1.0 leaves the distribution untouched
    cfg = SamplingConfig(temperature=1.0, top_p=1.0)
    toks = {int(sample(logits, cfg, jax.random.fold_in(key, i))[0])
            for i in range(200)}
    assert {0, 1, 2} <= toks
