"""Copy-on-write prefix sharing: fork bit-exactness vs unshared re-prefill,
refcount lifecycle, CoW tail isolation, spill/resume of shared pages,
multi-turn sessions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import greedy_reference

from repro.configs import get_smoke_config
from repro.core.paged import PAGE_TOKENS, pages_for
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.serving.api import Engine, ServeConfig
from repro.serving.memory import PagedStatePool
from repro.serving.scheduler import SchedulerConfig


@pytest.fixture(scope="module")
def tiny_fp32():
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(scope="module")
def hybrid_fp32():
    cfg = get_smoke_config("zamba2-2.7b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(3), cfg)
    return params, cfg


def _paged(params, cfg, **kw):
    base = dict(batch=3, n_pages=9, n_slabs=7)
    base.update(kw)
    return Engine(params, cfg, ServeConfig(backend="paged", **base))


def _full_context(parent, child):
    """The token sequence a forked child's decode is conditioned on."""
    return np.concatenate([
        np.asarray(parent.request.prompt, np.int32),
        np.asarray(parent.output, np.int32),
        np.asarray(child.request.prompt, np.int32)])


# ---------------------------------------------------------------------------
# pool-level: fork shares physical pages, decode rows agree bitwise
# ---------------------------------------------------------------------------

def test_pool_fork_shares_pages_and_logits_bit_identical(tiny_fp32):
    params, cfg = tiny_fp32
    pool = PagedStatePool(cfg, n_pages=9, n_slabs=5)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    pr = jnp.asarray(prompt)[None]
    logits, row = jax.jit(lambda p, b: M.prefill(p, cfg, b))(
        params, {"tokens": pr, "targets": pr})
    assert pool.register(1, pages_for(len(prompt)))
    pool.insert_prefill(1, row)
    before = pool.pages_allocated
    assert pool.fork(1, 2, len(prompt))
    # CoW cost: one private tail page, prefix shared by reference
    assert pool.pages_allocated == before + 1
    assert pool.page_table[2][0] == pool.page_table[1][0]       # shared
    assert pool.page_table[2][1] != pool.page_table[1][1]       # copied tail
    assert pool.shared_page_savings == 1
    tok = int(jnp.argmax(logits[0]))
    lg = pool.decode(params, [1, 2, None],
                     np.array([tok, tok, 0], np.int32),
                     np.array([140, 140, 0], np.int32), seed=7)
    np.testing.assert_array_equal(np.asarray(lg[0]), np.asarray(lg[1]))
    pool.release(1)
    assert pool.shared_page_savings == 0     # child now sole owner
    pool.release(2)
    assert pool.free_pages == pool.usable_pages


def test_pool_fork_at_page_boundary_copies_nothing(tiny_fp32):
    params, cfg = tiny_fp32
    pool = PagedStatePool(cfg, n_pages=9, n_slabs=5)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, PAGE_TOKENS).astype(np.int32)
    pr = jnp.asarray(prompt)[None]
    _, row = jax.jit(lambda p, b: M.prefill(p, cfg, b))(
        params, {"tokens": pr, "targets": pr})
    assert pool.register(1, 1)
    pool.insert_prefill(1, row)
    before = pool.pages_allocated
    assert pool.fork(1, 2, PAGE_TOKENS)
    assert pool.pages_allocated == before    # zero new pages
    assert pool.page_table[2] == pool.page_table[1]
    assert pool.shared_page_savings == 1


# ---------------------------------------------------------------------------
# engine-level: forked continuations == unshared re-prefill, exactly
# ---------------------------------------------------------------------------

def test_fork_matches_unshared_reprefill(tiny_fp32):
    params, cfg = tiny_fp32
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    turn = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)

    eng = _paged(params, cfg)
    parent = eng.submit(prompt, max_new_tokens=2, retain=True)
    parent.result()
    assert parent.status == "done"
    child = eng.fork(parent, turn, max_new_tokens=5)
    child.result()
    # no re-prefill happened: only the parent's prompt plus the child's
    # streamed continuation tokens were ever ingested
    st = eng.stats()
    assert st["prefill_tokens"] == len(prompt) + len(turn) + 1
    assert st["shared_page_hits"] == 1

    # the acceptance reference is the unshared *dense* re-prefill path:
    # the full context prefilled into contiguous caches -- no pages, no
    # sharing, no chunking
    context = _full_context(parent, child)
    ref = greedy_reference(params, cfg, context, 5)
    assert child.output == ref, (child.output, ref)
    # sharing saved prefill work vs the unshared run, which prefills the
    # whole context
    assert st["prefill_tokens"] - len(prompt) < len(context)


def test_parallel_forks_share_prefix_and_agree(tiny_fp32):
    """N sampled continuations of one prompt: all children share the full
    prefix pages; with greedy sampling they must agree token-for-token."""
    params, cfg = tiny_fp32
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    eng = _paged(params, cfg)
    parent = eng.submit(prompt, max_new_tokens=1, retain=True)
    parent.result()
    kids = [eng.fork(parent, max_new_tokens=4) for _ in range(2)]
    eng.run()
    assert kids[0].output == kids[1].output
    assert all(k.status == "done" for k in kids)
    st = eng.stats()
    # 2 pages (parent) + 1 tail copy per child; prefix page never re-alloced
    assert st["pages_allocated"] == 2 + 2
    assert st["shared_page_hits"] == 2
    # vs 2 independent submissions: 2 * 2 pages just for the prompts
    assert st["pages_allocated"] < 2 * pages_for(len(prompt) + 5) + 2


def test_fork_tail_copy_isolates_parent(tiny_fp32):
    """A child's appends go to its private tail copy: forking the same
    parent again after the first child ran must see pristine state."""
    params, cfg = tiny_fp32
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, 135).astype(np.int32)
    eng = _paged(params, cfg)
    parent = eng.submit(prompt, max_new_tokens=1, retain=True)
    parent.result()
    first = eng.fork(parent, max_new_tokens=5)
    first.result()
    second = eng.fork(parent, max_new_tokens=5)
    second.result()
    assert first.output == second.output, (first.output, second.output)


def test_refcounts_drop_to_zero_after_all_owners(tiny_fp32):
    params, cfg = tiny_fp32
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    eng = _paged(params, cfg)
    parent = eng.submit(prompt, max_new_tokens=1, retain=True)
    parent.result()
    kids = [eng.fork(parent, max_new_tokens=3) for _ in range(2)]
    # drive until both children hold their shared references
    while any(k.status == "queued" for k in kids):
        eng.step()
    pool = eng.engine.pool
    prefix_page = pool.page_table[parent.rid][0]
    assert pool.placement.refcount(prefix_page) == 3
    assert pool.shared_page_savings == 2
    eng.run()
    assert pool.placement.refcount(prefix_page) == 1   # parent only
    eng.release(parent)
    assert pool.placement.refcount(prefix_page) == 0
    assert pool.shared_page_savings == 0
    assert pool.free_pages == pool.usable_pages
    assert pool.free_slabs == pool.n_slabs - 1


def test_spill_resume_with_shared_pages_bit_exact(tiny_fp32):
    """Preempt a fork holding shared prefix pages: the shared page must not
    leave the device (the co-owners keep it), resume must continue
    bit-exactly, and the final tokens must equal the unshared reference."""
    params, cfg = tiny_fp32
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    # pool sized so the urgent late arrival forces preempting a fork:
    # parent 2 pages + 2 fork tails + 1 for the short prompt = 5 > 4 usable
    eng = _paged(params, cfg, batch=3, n_pages=5, n_slabs=7,
                 scheduler=SchedulerConfig(policy="priority"))
    parent = eng.submit(prompt, max_new_tokens=1, retain=True)
    parent.result()
    kids = [eng.fork(parent, max_new_tokens=10, priority=2)
            for _ in range(2)]
    while any(k.status == "queued" for k in kids):
        eng.step()
    urgent = eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                        max_new_tokens=2, priority=0)
    while not eng.engine.spilled and eng.step():
        pass
    assert eng.engine.spilled, "urgent arrival did not preempt a fork"
    sp = next(iter(eng.engine.spilled.values()))[0]
    assert sp.shared, "spilled fork held no shared pages"
    assert sp.pages_needed < sp.n_pages     # shared pages stayed resident
    eng.run()
    assert eng.engine.preemptions >= 1
    assert urgent.status == "done"
    assert all(k.status == "done" and len(k.output) == 10 for k in kids)
    assert kids[0].output == kids[1].output  # resumed == never-preempted

    ref_eng = _paged(params, cfg)
    ref = ref_eng.submit(_full_context(parent, kids[0]), max_new_tokens=10)
    ref.result()
    assert kids[0].output == ref.output, (kids[0].output, ref.output)


def test_fork_hybrid_model_copies_recurrent_state(hybrid_fp32):
    """Hybrid arch (attention pages + SSM slabs): the fork's slab copy must
    hand the child the exact recurrent state at the parent's length."""
    params, cfg = hybrid_fp32
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    turn = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    eng = _paged(params, cfg, batch=2)
    parent = eng.submit(prompt, max_new_tokens=1, retain=True)
    parent.result()
    child = eng.fork(parent, turn, max_new_tokens=4)
    child.result()
    ref_eng = _paged(params, cfg, batch=2)
    ref = ref_eng.submit(_full_context(parent, child), max_new_tokens=4)
    ref.result()
    assert child.output == ref.output, (child.output, ref.output)


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------

def test_session_multi_turn_matches_full_reprefill(tiny_fp32):
    params, cfg = tiny_fp32
    rng = np.random.default_rng(8)
    turns = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
             for n in (30, 6, 5)]
    eng = _paged(params, cfg)
    chat = eng.session()
    handles = []
    context = []
    for t in turns:
        h = chat.send(t, max_new_tokens=3)
        h.result()
        assert h.status == "done"
        handles.append(h)
        context.extend(map(int, t))
        # the reply to the conversation so far must equal a from-scratch
        # re-prefill of the whole history
        ref_eng = _paged(params, cfg)
        ref = ref_eng.submit(np.asarray(context, np.int32), max_new_tokens=3)
        ref.result()
        assert h.output == ref.output, (h.output, ref.output)
        context.extend(h.output)
    # only the newest turn stays retained; closing frees everything
    pool = eng.engine.pool
    assert len(eng.engine.retained) == 1
    chat.close()
    assert pool.free_pages == pool.usable_pages
    # the whole 3-turn chat never re-ingested history
    total_sent = sum(len(t) for t in turns)
    st = eng.stats()
    assert st["prefill_tokens"] <= total_sent + 2 * 1  # + fed parent tokens
