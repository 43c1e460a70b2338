"""Speculative decoding: greedy exactness, state rollback, draft sources,
acceptance accounting, and mid-speculation teardown.

The load-bearing guarantee is *greedy exactness*: with speculation on, the
emitted token stream is bit-identical to non-speculative paged decoding --
drafts only decide how many of the model's own tokens one fused verify pass
may confirm.  The parity matrix below pins that across attention (llama),
SSM (mamba2) and hybrid shared-attention (zamba2) architectures, on both
the pallas/mx8 and jnp/fp32 paths, for both draft sources.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import CELL, cell_state_config

from repro.configs import get_smoke_config
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.serving.api import Engine, ServeConfig
from repro.serving.engine import PagedServingEngine, Request
from repro.serving.memory import PAGE_TOKENS, PagedStatePool, pages_for
from repro.serving.sampler import SamplingConfig
from repro.serving.spec import KController, ModelDraft, NGramDraft

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container has no hypothesis; CI installs it
    HAVE_HYPOTHESIS = False


_CACHE = {}


def _build(arch, fmt="fp32", backend="jnp"):
    key = (arch, fmt, backend)
    if key not in _CACHE:
        # a cell's state heads run MX8 through the pallas kernel
        cfg = (cell_state_config(arch[:-len(CELL)]) if arch.endswith(CELL)
               else get_smoke_config(arch).with_(
                   state_quant=StateQuantConfig(fmt=fmt, rounding="nearest",
                                                backend=backend)))
        _CACHE[key] = (M.init_model(jax.random.PRNGKey(0), cfg), cfg)
    return _CACHE[key]


def _serve(params, cfg, prompts, spec, max_new=5, spec_k=3, **kw):
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2, n_pages=17, n_slabs=5, prefill_chunk=128,
        spec=spec, spec_k=spec_k, **kw))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=max_new))
    done = eng.run()
    return eng, {r.rid: list(r.output) for r in done}


def _prompts(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in (12, 9)]


# ---------------------------------------------------------------------------
# greedy exactness: the parity matrix
# ---------------------------------------------------------------------------

PARITY_MATRIX = [
    ("llama3.2-1b", "fp32", "jnp"),
    ("llama3.2-1b", "mx8", "pallas"),
    ("mamba2-2.7b", "fp32", "jnp"),
    ("mamba2-2.7b", "mx8", "pallas"),
    ("zamba2-2.7b", "fp32", "jnp"),
    ("zamba2-2.7b", "mx8", "pallas"),
]


@pytest.mark.parametrize("arch,fmt,backend", PARITY_MATRIX)
def test_spec_ngram_greedy_bit_identical(arch, fmt, backend):
    params, cfg = _build(arch, fmt, backend)
    prompts = _prompts(cfg)
    _, ref = _serve(params, cfg, prompts, spec=None)
    eng, out = _serve(params, cfg, prompts, spec="ngram")
    assert out == ref, (arch, fmt, backend)
    st = eng.stats()
    assert st["accepted_tokens"] <= st["proposed_tokens"]


# the model-draft source drives the identical verify/rollback machinery, so
# one pallas config suffices on top of the per-family jnp coverage
MODEL_DRAFT_MATRIX = [
    ("llama3.2-1b", "fp32", "jnp"),
    ("llama3.2-1b", "mx8", "pallas"),
    ("mamba2-2.7b", "fp32", "jnp"),
    ("zamba2-2.7b", "fp32", "jnp"),
]


@pytest.mark.parametrize("arch,fmt,backend", MODEL_DRAFT_MATRIX)
def test_spec_model_draft_greedy_bit_identical(arch, fmt, backend):
    params, cfg = _build(arch, fmt, backend)
    prompts = _prompts(cfg)
    _, ref = _serve(params, cfg, prompts, spec=None, max_new=4)
    eng, out = _serve(params, cfg, prompts, spec="model:llama3.2-1b",
                      max_new=4)
    assert out == ref, (arch, fmt, backend)
    # same arch + same params seed drafts for itself on the jnp path, but
    # exactness must hold whatever the draft proposes -- no acceptance gate


# ---------------------------------------------------------------------------
# pool-level verify parity + bit-exact rollback of rejected positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,length", [("mamba2-2.7b", 127),
                                         ("zamba2-2.7b", 128),
                                         ("mamba2-2.7b" + CELL, 127)])
def test_spec_verify_positions_and_rollback_bit_exact(arch, length, n=3):
    """decode_spec position i's logits == the i-th sequential decode step,
    and commit_spec restores the state slab of *exactly* the selected
    position: all-accept equals n sequential steps, sel=0 equals one."""
    params, cfg = _build(arch)
    pool = PagedStatePool(cfg, n_pages=10, n_slabs=5)
    rng = np.random.default_rng(length)
    prompt = rng.integers(0, cfg.vocab_size, length).astype(np.int32)
    pr = jnp.asarray(prompt)[None]
    logits, row = jax.jit(lambda p, b: M.prefill(p, cfg, b))(
        params, {"tokens": pr, "targets": pr})
    assert pool.register(1, pages_for(length))
    pool.insert_prefill(1, row)
    tok = int(jnp.argmax(logits[0]))

    # copies, not views: the pools are donated into later jitted steps, so
    # a zero-copy np.asarray view would read reused buffers
    snapshot = [np.array(x) for x in pool.pools]
    pages0 = list(pool.page_table[1])

    def slab_rows(pools):
        s = pool.slab_of[1]
        return [np.array(p[s]) for p, spec
                in zip(pools, pool.paging.specs) if spec.kind == "slab"]

    def rewind():
        grown = [p for p in pool.page_table[1] if p not in pages0]
        if grown:
            pool.placement.free(grown)
        pool.page_table[1] = list(pages0)
        pool.pools = [jnp.asarray(x) for x in snapshot]

    # sequential reference: n steps, seeds 1..n
    seq_logits, toks = [], [tok]
    L = np.array([length, 0], np.int32)
    for step in range(n):
        while L[0] // PAGE_TOKENS + 1 > len(pool.page_table[1]):
            assert pool.grow(1, 1)
        lg = pool.decode(params, [1, None],
                         np.array([toks[-1], 0], np.int32), L, seed=step + 1)
        seq_logits.append(np.array(lg))
        toks.append(int(jnp.argmax(lg[0])))
        L[0] += 1
    seq_slabs = slab_rows(pool.pools)

    # one verify pass over the same n tokens at seed 1 (per-position seeds
    # seed + i match the sequential steps' 1..n)
    rewind()
    while pages_for(length + n) > len(pool.page_table[1]):
        assert pool.grow(1, 1)
    tokens = np.array([toks[:n], [0] * n], np.int32)
    lengths = np.array([length, 0], np.int32)
    lg, snaps = pool.decode_spec(params, [1, None], tokens, lengths, seed=1,
                                 min_pages=pages_for(length + n))
    lg = np.array(lg)
    for i in range(n):
        np.testing.assert_array_equal(lg[:1, i], seq_logits[i][:1],
                                      err_msg=f"position {i}")

    # all-accept: slab rows == n sequential steps
    pool.commit_spec([1, None], snaps, np.array([n - 1, 0], np.int32))
    for a, b in zip(slab_rows(pool.pools), seq_slabs):
        np.testing.assert_array_equal(a, b)

    # rollback to position 0: slab rows == exactly one sequential step
    rewind()
    while pages_for(length + n) > len(pool.page_table[1]):
        assert pool.grow(1, 1)
    _, snaps2 = pool.decode_spec(params, [1, None], tokens, lengths, seed=1,
                                 min_pages=pages_for(length + n))
    pool.commit_spec([1, None], snaps2, np.array([0, 0], np.int32))
    rolled = slab_rows(pool.pools)
    rewind()
    while length // PAGE_TOKENS + 1 > len(pool.page_table[1]):
        assert pool.grow(1, 1)
    pool.decode(params, [1, None], np.array([toks[0], 0], np.int32),
                np.array([length, 0], np.int32), seed=1)
    for a, b in zip(rolled, slab_rows(pool.pools)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# acceptance accounting + stream ordering
# ---------------------------------------------------------------------------

def test_spec_acceptance_accounting_and_stream_order():
    """Per-run invariants of the acceptance counters, and the stream is
    append-only: tokens surface through the handle in emit order and an
    earlier read is always a prefix of a later one (sampled mode included --
    only greedy promises *which* tokens, every mode promises the order)."""
    params, cfg = _build("llama3.2-1b")
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    prompt = np.concatenate([base, base, base]).astype(np.int32)
    for temp in (0.0, 0.8):
        eng = Engine(params, cfg, ServeConfig(
            backend="paged", batch=2, n_pages=17, n_slabs=5,
            sampling=SamplingConfig(temperature=temp, top_p=0.9),
            spec="ngram", spec_k=3))
        h = eng.submit(prompt, max_new_tokens=16)
        seen = []
        while eng.step():
            out = h.output
            assert out[:len(seen)] == seen, "token stream reordered"
            seen = out
        assert h.status == "done" and len(h.output) == 16
        st = eng.stats()
        assert 0 <= st["accepted_tokens"] <= st["proposed_tokens"]
        assert 0.0 <= st["acceptance_rate"] <= 1.0
        if st["proposed_tokens"]:
            assert st["accepted_tokens_per_step"] >= 1.0


def test_spec_stats_schema_stable_when_off():
    params, cfg = _build("llama3.2-1b")
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2, n_pages=9, n_slabs=5, prefill_chunk=128))
    eng.submit(Request(rid=0, prompt=_prompts(cfg)[1], max_new_tokens=2))
    eng.run()
    st = eng.stats()
    for key in ("proposed_tokens", "accepted_tokens", "acceptance_rate",
                "accepted_tokens_per_step"):
        assert st[key] == 0.0, key


# ---------------------------------------------------------------------------
# draft sources and the k-controller (host-side, model-free)
# ---------------------------------------------------------------------------

def test_ngram_draft_proposes_the_repeating_continuation():
    d = NGramDraft()
    d.admit(0, [])
    ctx = [1, 2, 3, 9, 1, 2, 3]
    assert d.propose(0, ctx, 2) == [9, 1]      # after the 3-gram [1, 2, 3]
    assert d.propose(0, [5, 6, 7], 3) == []    # nothing repeats
    d.release(0)
    assert d.propose(0, ctx, 2) == []          # released rids never propose


def test_kcontroller_decays_and_recovers():
    k = KController(k_max=4, window=4)
    assert k.k_for(0) == 4                     # optimistic start
    for _ in range(4):
        k.observe(0, 4, 0)
    assert k.k_for(0) == 1                     # full rejection decays to 1
    for _ in range(4):
        k.observe(0, 4, 4)
    assert k.k_for(0) == 4                     # full acceptance climbs back
    k.observe(0, 0, 0)                         # no drafts = no evidence
    assert k.k_for(0) == 4
    k.forget(0)
    assert k.k_for(0) == 4


def test_model_draft_catchup_and_rollback_counter():
    params, cfg = _build("llama3.2-1b")
    d = ModelDraft(cfg, params, max_requests=2, max_len=512)
    prompt = list(map(int, _prompts(cfg)[0]))
    assert d.admit(1, prompt)
    out1 = d.propose(1, prompt, 3)
    assert len(out1) == 3 and d.consumed[1] == len(prompt)
    # rejected drafts are behind the counter: the next call re-proposes from
    # the verified context and the first draft is reproducible
    out2 = d.propose(1, prompt, 3)
    assert out2 == out1
    # accepted tokens arrive as context; the draft catches up, then drafts
    out3 = d.propose(1, prompt + out1[:2], 2)
    assert len(out3) == 2
    d.release(1)
    assert 1 not in d.consumed
    d.sanitizer_check_leaks()                  # pages freed with the rid


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.integers(0, 7), min_size=1, max_size=40),
           st.integers(1, 5))
    def test_prop_ngram_proposal_is_a_witnessed_continuation(ctx, k):
        """Whatever propose returns actually follows an earlier occurrence
        of the context's trailing gram, and never exceeds k tokens."""
        d = NGramDraft()
        d.admit(0, [])
        out = d.propose(0, ctx, k)
        assert 0 <= len(out) <= k
        if out:
            n = len(ctx)
            witnessed = False
            for g in range(min(d.max_gram, n - 1), 0, -1):
                tail = ctx[n - g:]
                for start in range(n - g - 1, -1, -1):
                    if (ctx[start:start + g] == tail
                            and ctx[start + g:start + g + len(out)] == out):
                        witnessed = True
            assert witnessed

    @settings(max_examples=60, deadline=None)
    @given(st.integers(1, 6), st.integers(1, 8),
           st.lists(st.tuples(st.integers(0, 6), st.integers(0, 6)),
                    max_size=30))
    def test_prop_kcontroller_bounds(k_max, window, history):
        """k_for stays in [1, k_max] under any observation history, and
        observations never record accepted > proposed evidence backwards."""
        k = KController(k_max=k_max, window=window)
        for proposed, accepted in history:
            k.observe(0, proposed, min(accepted, proposed))
            assert 1 <= k.k_for(0) <= k_max
        assert 1 <= k.k_for(0) <= k_max
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_prop_ngram_proposal_is_a_witnessed_continuation():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_prop_kcontroller_bounds():
        pass


# ---------------------------------------------------------------------------
# mid-speculation teardown: abort, preempt, chaos alloc
# ---------------------------------------------------------------------------

def test_spec_abort_mid_speculation_unwinds_cleanly():
    """Aborting a request mid-speculation frees its target pages AND its
    draft-model state: drafted-but-unverified tokens die with the draft
    (they were never in the output), and the drained engine passes the
    shadow-ledger teardown for both pools."""
    params, cfg = _build("llama3.2-1b")
    prompts = _prompts(cfg)
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2, n_pages=40, n_slabs=5, prefill_chunk=128,
        spec="model:llama3.2-1b", spec_k=3))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=12))
    while not (len(eng.active) == 2
               and all(len(a.req.output) >= 2
                       for a in eng.active.values())):
        assert eng.step()
    assert 0 in eng.draft.consumed             # mid-speculation, draft live
    assert eng.abort(0)
    assert 0 not in eng.draft.consumed         # draft state went with it
    eng.run()
    done = {r.rid: r for r in eng.done}
    assert done[0].status == "aborted"
    assert done[1].status == "done"
    # parity for the survivor: same tokens as a clean non-spec run
    _, ref = _serve(params, cfg, prompts, spec=None, max_new=12)
    assert list(done[1].output) == ref[1]
    eng.draft.sanitizer_check_leaks()


def test_spec_preempt_mid_speculation_stays_bit_exact():
    """Preempting a speculating request spills, resumes, and still emits
    the exact greedy stream; the draft source is suspended and lazily
    re-admitted after resume."""
    params, cfg = _build("llama3.2-1b")
    prompts = _prompts(cfg)
    _, ref = _serve(params, cfg, prompts, spec=None, max_new=8)
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=2, n_pages=17, n_slabs=5, prefill_chunk=128,
        spec="ngram", spec_k=3))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=8))
    while not any(len(a.req.output) >= 2 for a in eng.active.values()):
        assert eng.step()
    rid = next(r for r, a in eng.active.items() if len(a.req.output) >= 2)
    eng._preempt(rid)
    done = {r.rid: list(r.output) for r in eng.run()}
    assert done == ref
    assert eng.preemptions >= 1


def test_spec_chaos_alloc_inside_verify_step():
    """A transient alloc fault during speculative headroom growth recovers
    (retry or preemption) without leaking pages or corrupting the stream."""
    params, cfg = _build("llama3.2-1b")
    prompts = _prompts(cfg)
    _, ref = _serve(params, cfg, prompts, spec=None, max_new=6)
    eng, out = _serve(params, cfg, prompts, spec="ngram", max_new=6,
                      fault_plan="alloc:nth=1")
    assert out == ref
    assert eng.obs.metrics.value("faults_recovered_total", site="alloc") >= 1
