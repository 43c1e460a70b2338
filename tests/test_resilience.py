"""Resilience layer: deterministic fault injection (repro.serving.faults)
and graceful degradation (repro.serving.resilience) -- every injected
fault maps to a documented recovery, non-faulted requests stay bit-exact,
and the engine always drains to terminal statuses.

The whole module runs under the tier-1 shadow-ledger sanitizer
(``REPRO_SANITIZE=1`` via conftest): any injected fault that leaks pages,
host pins, or staged prefetches raises ``SanitizerError`` immediately.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.serving.api import Engine, ServeConfig
from repro.serving.engine import TERMINAL_STATUSES
from repro.serving.faults import SITES, FaultPlan, FaultSpecError
from repro.serving.resilience import (BlobCorruption, LADDER, StepWatchdog,
                                      corrupt_blob, crc_blob,
                                      retry_transient, verify_blob)
from repro.serving.sampler import SamplingConfig
from repro.serving.scheduler import SchedulerConfig
from repro.analysis.lint.runtime import SanitizerError


# ---------------------------------------------------------------------------
# FaultPlan: spec grammar + deterministic triggers
# ---------------------------------------------------------------------------

def test_fault_spec_parses_every_site():
    plan = FaultPlan(";".join(SITES))
    assert set(plan.rules) == set(SITES)
    assert plan.total_injected == 0


@pytest.mark.parametrize("bad", [
    "", "   ", "frobnicate:nth=1", "alloc:nth", "alloc:nth=",
    "alloc:wat=3", "nan:p=1.5", "alloc:nth=1;alloc:nth=2",
])
def test_fault_spec_rejects_malformed(bad):
    with pytest.raises(FaultSpecError):
        FaultPlan(bad)


def test_nth_trigger_fires_exactly_once():
    plan = FaultPlan("alloc:nth=3")
    fired = [plan.should_fire("alloc") for _ in range(6)]
    assert fired == [False, False, True, False, False, False]
    assert plan.injected["alloc"] == 1


def test_step_trigger_tracks_engine_step():
    plan = FaultPlan("alloc:step=2")
    plan.set_step(1)
    assert not plan.should_fire("alloc")
    plan.set_step(2)
    assert plan.should_fire("alloc")
    assert not plan.should_fire("alloc")      # one-shot by default


def test_rid_trigger_and_cap():
    plan = FaultPlan("nan:rid=3,n=2")
    assert not plan.should_fire("nan", rid=1)
    assert plan.should_fire("nan", rid=3)
    assert plan.should_fire("nan", rid=3)
    assert not plan.should_fire("nan", rid=3)  # n=2 cap reached
    assert plan.injected["nan"] == 2


def test_unlisted_site_never_fires():
    plan = FaultPlan("alloc:nth=1")
    assert not plan.should_fire("host_pin")
    assert plan.should_fire("alloc")


def test_probabilistic_trigger_is_seed_deterministic():
    a = FaultPlan("alloc:p=0.5", seed=7)
    b = FaultPlan("alloc:p=0.5", seed=7)
    c = FaultPlan("alloc:p=0.5", seed=8)
    seq_a = [a.should_fire("alloc") for _ in range(64)]
    seq_b = [b.should_fire("alloc") for _ in range(64)]
    seq_c = [c.should_fire("alloc") for _ in range(64)]
    assert seq_a == seq_b                      # same seed, same schedule
    assert seq_a != seq_c                      # different seed diverges
    assert 0 < sum(seq_a) < 64


def test_plan_from_env_and_maybe_precedence():
    assert FaultPlan.from_env(env={}) is None
    plan = FaultPlan.from_env(env={"REPRO_FAULTS": "nan:rid=1"}, seed=3)
    assert plan is not None and plan.seed == 3
    assert FaultPlan.maybe(None, use_env=False) is None
    explicit = FaultPlan.maybe("alloc:nth=1", seed=2)
    assert explicit is not None and "alloc" in explicit.rules
    assert plan.param("slow_step", "ms", default=9.0) == 9.0
    assert FaultPlan("slow_step:ms=250").param("slow_step", "ms") == 250.0


# ---------------------------------------------------------------------------
# resilience primitives: checksums, bounded retry, watchdog
# ---------------------------------------------------------------------------

def test_blob_crc_roundtrip_detects_single_byte_flip():
    blob = [np.arange(12, dtype=np.float32).reshape(3, 4),
            np.zeros(5, np.int32)]
    crc = crc_blob(blob)
    verify_blob(blob, crc, "spill blob")           # clean: no raise
    verify_blob(blob, None, "legacy blob")         # unchecked: no raise
    corrupt_blob(blob)
    with pytest.raises(BlobCorruption) as ei:
        verify_blob(blob, crc, "spill blob", rid=7)
    assert ei.value.rid == 7 and "spill blob" in str(ei.value)


def test_crc_is_shape_sensitive():
    a = [np.arange(12, dtype=np.float32).reshape(3, 4)]
    b = [np.arange(12, dtype=np.float32).reshape(4, 3)]
    assert crc_blob(a) != crc_blob(b)


def test_corrupt_blob_handles_readonly_views():
    arr = np.arange(8, dtype=np.float32)
    arr.setflags(write=False)
    blob = [arr]
    crc = crc_blob(blob)
    corrupt_blob(blob)                      # must not raise on readonly
    assert crc_blob(blob) != crc


def test_retry_transient_bounded():
    calls = []

    def flaky():
        calls.append(1)
        return len(calls) >= 3

    retries = []
    assert retry_transient(flaky, attempts=4,
                           on_retry=retries.append) is True
    assert len(calls) == 3 and retries == [1, 2]

    assert retry_transient(lambda: False, attempts=3) is False

    def boom():
        raise RuntimeError("real fault")
    with pytest.raises(RuntimeError):       # exceptions are not transient
        retry_transient(boom)


def test_watchdog_flags_only_over_budget():
    wd = StepWatchdog(None)
    assert not wd.enabled and not wd.observe(0, 1e9)
    wd = StepWatchdog(0.1)
    assert not wd.observe(0, 0.05)
    assert wd.observe(1, 0.25) and wd.trips == 1
    assert wd.slowest_s == 0.25
    assert tuple(LADDER) == ("drop_prefix", "demote_store", "preempt",
                             "shed")


# ---------------------------------------------------------------------------
# engine-level fault -> recovery (small real model, greedy = bit-exact)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama():
    cfg = get_smoke_config("llama3.2-1b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


_GREEDY = SamplingConfig(temperature=0.0)


def _batch_engine(llama, fault_plan=None, **kw):
    cfg, params = llama
    return Engine(params, cfg, ServeConfig(
        backend="paged", batch=2, n_pages=17, n_slabs=5, sampling=_GREEDY,
        fault_plan=fault_plan, **kw))


def _run_batch(llama, fault_plan=None, **kw):
    cfg, _ = llama
    rng = np.random.default_rng(0)
    eng = _batch_engine(llama, fault_plan=fault_plan, **kw)
    hs = [eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                     max_new_tokens=5) for n in (10, 14, 18)]
    eng.run()
    return eng, hs


@pytest.fixture(scope="module")
def baseline(llama):
    """Fault-free reference outputs for the 3-request batch workload."""
    eng, hs = _run_batch(llama)
    assert [h.status for h in hs] == ["done"] * 3
    return [h.output for h in hs]


def test_disabled_faults_cost_nothing(llama, baseline):
    eng, hs = _run_batch(llama)
    assert eng.engine.faults is None          # no plan installed
    assert not eng.engine._nan_guard          # no per-step finite scan
    assert not eng.engine.watchdog.enabled    # no wall-clock checks
    assert [h.output for h in hs] == baseline


@pytest.mark.slow
def test_nan_quarantines_only_the_poisoned_request(llama, baseline):
    eng, hs = _run_batch(llama, fault_plan="nan:rid=1")
    assert hs[1].status == "failed"
    assert "non-finite" in hs[1].request.detail
    # the other rows of the same decode batch are untouched, bit for bit
    assert hs[0].status == "done" and hs[0].output == baseline[0]
    assert hs[2].status == "done" and hs[2].output == baseline[2]
    assert eng.engine.faults.injected["nan"] == 1
    m = eng.obs.metrics
    assert m.value("quarantines_total") == 1
    assert eng.stats()["requests_failed"] == 1


@pytest.mark.slow
def test_transient_alloc_is_retried_transparently(llama, baseline):
    eng, hs = _run_batch(llama, fault_plan="alloc:nth=1")
    assert [h.status for h in hs] == ["done"] * 3
    assert [h.output for h in hs] == baseline
    m = eng.obs.metrics
    assert m.value("fault_retries_total", site="alloc") >= 1
    assert m.value("faults_recovered_total", site="alloc") >= 1


@pytest.mark.slow
def test_slow_step_trips_watchdog_without_dropping_work(llama, baseline):
    eng, hs = _run_batch(llama, fault_plan="slow_step:step=1,ms=80",
                         step_budget_s=0.05)
    assert eng.engine.watchdog.trips >= 1
    assert [h.output for h in hs] == baseline
    assert eng.obs.metrics.value("watchdog_trips_total") >= 1


def _preempt_engine(llama, fault_plan=None):
    cfg, params = llama
    return Engine(params, cfg, ServeConfig(
        backend="paged", batch=1, n_pages=9, n_slabs=5, sampling=_GREEDY,
        scheduler=SchedulerConfig(policy="priority"),
        fault_plan=fault_plan))


def _run_preempted(llama, fault_plan):
    """Long request B preempted by urgent A: exercises spill -> host pin
    -> (staged prefetch ->) resume with the given plan."""
    cfg, _ = llama
    rng = np.random.default_rng(2)
    prompt_b = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    prompt_a = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    eng = _preempt_engine(llama, fault_plan)
    hb = eng.submit(prompt_b, max_new_tokens=8, priority=5)
    while hb.status == "queued" and eng.step():
        pass
    ha = eng.submit(prompt_a, max_new_tokens=6, priority=0)
    eng.engine._preempt(hb.rid)
    eng.run()
    return eng, ha, hb


@pytest.fixture(scope="module")
def preempt_ref(llama):
    """B's outputs served alone, never preempted, never faulted."""
    cfg, _ = llama
    rng = np.random.default_rng(2)
    prompt_b = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    eng = _preempt_engine(llama)
    return eng.submit(prompt_b, max_new_tokens=8, priority=5
                      ).result().output


@pytest.mark.slow
def test_corrupt_spill_blob_recovers_by_reprefill(llama, preempt_ref):
    eng, ha, hb = _run_preempted(llama, "blob_corrupt:nth=1")
    assert ha.status == "done" and hb.status == "done"
    assert hb.output == preempt_ref          # re-prefill is bit-exact
    m = eng.obs.metrics
    assert m.value("blob_corruptions_total") == 1
    assert m.value("faults_recovered_total", site="blob_corrupt") == 1
    assert eng.engine.pool.host.pinned_bytes == 0


@pytest.mark.slow
def test_transient_host_pin_never_drops_live_state(llama, preempt_ref):
    eng, ha, hb = _run_preempted(llama, "host_pin:nth=1")
    assert ha.status == "done" and hb.status == "done"
    assert hb.output == preempt_ref
    assert eng.engine.faults.injected["host_pin"] == 1
    assert eng.engine.pool.host.pinned_bytes == 0


@pytest.mark.slow
def test_failed_prefetch_commit_falls_back_to_sync_resume(llama,
                                                          preempt_ref):
    eng, ha, hb = _run_preempted(llama, "prefetch_commit:nth=1")
    assert ha.status == "done" and hb.status == "done"
    assert hb.output == preempt_ref
    m = eng.obs.metrics
    assert m.value("faults_recovered_total", site="prefetch_commit") == 1
    assert eng.engine.pool.host.pinned_bytes == 0


# ---------------------------------------------------------------------------
# satellite 1: abort with an in-flight prefetch leaks nothing
# ---------------------------------------------------------------------------

def test_abort_with_inflight_prefetch_unpins_and_teardown_is_clean():
    cfg = get_smoke_config("mamba2-2.7b").with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    eng = Engine(params, cfg, ServeConfig(
        backend="paged", batch=1, n_pages=9, n_slabs=5, sampling=_GREEDY,
        scheduler=SchedulerConfig(policy="priority")))
    hb = eng.submit(rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
                    max_new_tokens=8, priority=5)
    while hb.status == "queued" and eng.step():
        pass
    ha = eng.submit(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4, priority=0)
    eng.engine._preempt(hb.rid)
    eng.step()                               # stages B's prefetch
    pool = eng.engine.pool
    assert pool._staged, "prefetch was not staged"
    # the leak the shadow ledger would flag: staged prefetch at teardown
    with pytest.raises(SanitizerError, match="^PL255"):
        pool.sanitizer_check_leaks("mid-flight check")
    hb.abort()                               # must cancel the prefetch too
    assert hb.status == "aborted"
    assert not pool._staged
    ha.result()
    assert ha.status == "done"
    assert pool.host.pinned_bytes == 0
    pool.sanitizer_check_leaks("post-abort")  # drained: no PL255


# ---------------------------------------------------------------------------
# satellite 2 + admission control: overload never wedges the engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_never_admittable_head_is_rejected_not_spun(llama):
    cfg, params = llama
    eng = Engine(params, cfg, ServeConfig(
        backend="paged", batch=1, n_pages=9, n_slabs=5, sampling=_GREEDY))
    rng = np.random.default_rng(4)
    # a retained request holds every usable page past completion (896
    # prompt tokens + generated tail = the full pool), so the next
    # request's admission can never succeed -- not even with the pool idle
    big = eng.submit(rng.integers(0, cfg.vocab_size, 896).astype(np.int32),
                     max_new_tokens=2, retain=True)
    big.result()
    assert big.status == "done"
    assert eng.engine.pool.free_pages == 0
    h = eng.submit(rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                   max_new_tokens=4)
    eng.run()                                # must terminate, not spin
    assert h.status == "rejected"
    assert "page budget" in h.request.detail
    assert eng.stats()["requests_rejected"] == 1


@pytest.mark.slow
def test_max_queued_sheds_at_the_door(llama, baseline):
    eng, hs = _run_batch(llama, max_queued=1)
    assert hs[0].status == "done" and hs[0].output == baseline[0]
    assert [h.status for h in hs[1:]] == ["rejected"] * 2
    assert all("max_queued" in h.request.detail for h in hs[1:])
    assert eng.stats()["requests_rejected"] == 2


@pytest.mark.slow
def test_request_timeout_expires_stale_queue_entries(llama):
    cfg, params = llama
    eng = Engine(params, cfg, ServeConfig(
        backend="paged", batch=1, n_pages=17, n_slabs=5, sampling=_GREEDY,
        request_timeout_s=50.0))
    rng = np.random.default_rng(5)
    ha = eng.submit(rng.integers(0, cfg.vocab_size, 10).astype(np.int32),
                    max_new_tokens=5)
    while ha.status == "queued" and eng.step():
        pass
    hb = eng.submit(rng.integers(0, cfg.vocab_size, 14).astype(np.int32),
                    max_new_tokens=5)
    hb.request.t_submit -= 100.0    # simulate 100 s already spent queued
    eng.run()
    # batch=1: A keeps the slot, B ages past the deadline while waiting
    assert ha.status == "done"
    assert hb.status == "rejected"
    assert "request_timeout_s" in hb.request.detail
    assert eng.obs.metrics.value("request_timeouts_total") >= 1


# ---------------------------------------------------------------------------
# seeded chaos: random plans under open-loop traffic always drain
# ---------------------------------------------------------------------------

def _random_plan(rng) -> str:
    clauses = []
    if rng.random() < 0.7:
        clauses.append(f"alloc:p={rng.uniform(0.05, 0.4):.2f}")
    if rng.random() < 0.5:
        clauses.append(f"nan:p={rng.uniform(0.02, 0.15):.2f}")
    if rng.random() < 0.5:
        clauses.append(f"slow_step:p={rng.uniform(0.1, 0.5):.2f},ms=1")
    if rng.random() < 0.5:
        clauses.append("host_pin:p=0.5")
    if rng.random() < 0.5:
        clauses.append("blob_corrupt:p=0.5")
    if rng.random() < 0.5:
        clauses.append("prefetch_commit:p=0.5")
    return ";".join(clauses) or "alloc:p=0.25"


@pytest.mark.slow
@pytest.mark.parametrize("seed", [11, 23])
def test_chaos_every_request_terminal_and_engine_drains(llama, seed):
    """Property test, hand-seeded (hypothesis is not in the image): a
    random fault plan under open-loop traffic leaves every request in a
    terminal status, the engine fully drained, and the shadow-ledger
    sanitizer (enabled module-wide) silent."""
    cfg, _ = llama
    rng = np.random.default_rng(seed)
    plan = _random_plan(rng)
    eng = _batch_engine(llama, fault_plan=plan)
    hs = []
    for _ in range(6):
        n = int(rng.integers(6, 24))
        hs.append(eng.submit(
            rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 6))))
        eng.step()                           # open loop: arrivals mid-run
    eng.run()
    statuses = [h.status for h in hs]
    assert all(s in TERMINAL_STATUSES for s in statuses), \
        f"non-terminal under plan {plan!r} (seed {seed}): {statuses}"
    assert not eng.engine.has_work()
    assert eng.engine.pool.host.pinned_bytes == 0
    done = [h for h in hs if h.status == "done"]
    assert done, f"plan {plan!r} starved every request"
