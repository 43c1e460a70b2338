"""The batched serving engine: the Pimba system loop (paper Fig. 7).

``PagedServingEngine`` serves from the paged pool (``serving/memory``):
state/KV memory is block/page granular with a block table per request, so
short and long prompts coexist in the same byte budget, admission follows a
priority/deadline scheduler (``serving/scheduler``), prefill is chunked
(the tail of a long prompt streams through the shared decode step instead
of blocking the batch), and the pool preempts by page eviction -- victim
pages spill to host bit-exactly and resume re-pins them.  It supports
**retained** requests (finished but still pinning their pages) and
copy-on-write ``fork`` of a retained parent: the child shares the parent's
full prefix pages by reference and skips re-prefill entirely (multi-turn
sessions, N parallel continuations of one prompt).

Its request lifecycle is an explicit ``step()`` event loop (admit + one
batched decode step) that callers can drive open-loop, ``submit`` /
``abort`` with terminal statuses (``done`` / ``aborted`` / ``truncated``
...), and a ``run()`` drain wrapper.  The streaming facade over it, and the
``ServeConfig`` it reads, live in :mod:`repro.serving.api`.

The cache pool is MX8 by default -- the 8-bit state is what makes state
memory ~2x smaller than the fp16 baseline (paper Fig. 1a, 15b).
"""
from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops as OPS
from repro.core.paged import PAGE_TOKENS, pages_for
from repro.models import model as M
from repro.obs import Observability
from repro.models.config import ModelConfig
from repro.serving.faults import FaultPlan
from repro.serving.memory import SpilledRequest, TieredStatePool
from repro.serving.resilience import (REPREFILL_CAP, BlobCorruption,
                                      StepWatchdog, retry_transient)
from repro.serving.sampler import SamplingConfig, filtered_probs, sample
from repro.serving.scheduler import Scheduler

if TYPE_CHECKING:
    from repro.serving.api import ServeConfig

#: terminal request statuses -- a request in one of these will never
#: produce another token.  ``failed`` = the engine quarantined it after an
#: unrecoverable fault (NaN logits, corruption past the re-prefill cap);
#: ``rejected`` = admission control shed it before it ever decoded.
TERMINAL_STATUSES = ("done", "aborted", "truncated", "failed", "rejected")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    priority: int = 0                  # lower = more urgent
    deadline: Optional[float] = None   # absolute time (EDF)
    retain: bool = False               # keep pages pinned after finish
                                       # (enables fork())
    parent_rid: Optional[int] = None   # copy-on-write fork parent
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    status: str = "new"                # new|queued|running|done|aborted|
                                       # truncated|failed|rejected
    detail: Optional[str] = None       # why a request failed / was rejected
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    truncated: bool = False            # ran out of pool pages mid-generation

    @property
    def finished(self) -> bool:
        return self.status in TERMINAL_STATUSES


class _OpTrafficMeter:
    """Accumulates per-op-kind SPU traffic over decode steps.

    Bytes come from the registered paged ops' own ``traffic(plan)``
    descriptors (``repro.ops.decode_traffic_by_kind``), which are affine in
    a row's *page count* (whole 128-token pages stream, appends write one
    slot).  They are probed once at one and two pages, and each step costs
    O(kinds), not O(rows) registry walks.  The engine passes
    pre-deduplicated page counts, so a copy-on-write shared page is
    attributed once per step, not once per reader.
    """

    def __init__(self, cfg: ModelConfig, metrics=None):
        self.cfg = cfg
        self.metrics = metrics        # mirror into the obs registry
        self.by_kind: Dict[str, float] = {}
        self._affine = None   # kind -> (bytes at 1 page, bytes per +1 page)

    def _coeffs(self) -> Dict[str, tuple]:
        if self._affine is None:
            t1, t2 = (OPS.decode_traffic_by_kind(self.cfg, 1, u, "paged")
                      for u in (PAGE_TOKENS, 2 * PAGE_TOKENS))
            self._affine = {k: (t1[k].total, t2[k].total - t1[k].total)
                            for k in t1}
        return self._affine

    def account_units(self, units: List[int]) -> None:
        if not units:
            return
        n, total = len(units), sum(units)
        for kind, (base, slope) in self._coeffs().items():
            add = n * base + (total - n) * slope
            self.by_kind[kind] = self.by_kind.get(kind, 0.0) + add
            if self.metrics is not None:
                self.metrics.counter("op_traffic_bytes_total",
                                     kind=kind).inc(add)

    def stats(self) -> Dict[str, float]:
        return {f"op_traffic_bytes/{k}": v
                for k, v in sorted(self.by_kind.items())}


def _sample_tokens(key, logits, sampling: SamplingConfig):
    """The one sampling helper the engine routes through (prefill's first
    token and every decode step): split the engine key once, sample a whole
    batch of logits.  Returns (new_key, tokens (B,) on device)."""
    key, sub = jax.random.split(key)
    return key, sample(logits, sampling, sub)


def _prefill_program(cfg: ModelConfig, mesh_axes):
    """The jitted full-sequence prefill, under a name of its own: the
    profiler shows it as ``jit_prefill``.  ``lengths`` (B,), when given,
    masks the padding after each row's real tokens (``M.prefill``)."""
    def prefill(params, batch, lengths=None):
        return M.prefill(params, cfg=cfg, batch=batch, mesh_axes=mesh_axes,
                         lengths=lengths)
    return jax.jit(prefill)


@dataclasses.dataclass
class _Active:
    req: Request
    length: int                       # cached positions so far
    pending: List[int]                # prompt tokens not yet consumed
    cur_token: int                    # next token to feed once prompt is done
    replayed: bool = False            # corruption-recovery re-prefill: the
                                      # "prompt" includes generated tokens,
                                      # so prefix-store inserts are skipped


class PagedServingEngine:
    """Continuous batching over the paged, bank-aware state/KV pool.

    The engine owns the public lifecycle: ``submit`` -> ``step``/``run``
    -> terminal status, plus ``abort`` and the stats schema.  It carries an
    :class:`repro.obs.Observability` bundle: ``stats()`` is a
    schema-stable view over its metrics registry, request phase transitions
    land in its lifecycle tracker, each step and the boundaries inside it
    are spans in its trace ring buffer (and in a running ``jax.profiler``
    session), and the jitted steppers are wrapped by its recompile watcher.
    """

    def __init__(self, params, cfg: ModelConfig, scfg: "ServeConfig",
                 mesh_axes=None, obs: Optional[Observability] = None):
        assert not cfg.encoder_only
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.obs = obs if obs is not None else Observability()
        self.done: List[Request] = []
        self.step_count = 0
        self.step_times: List[float] = []
        #: parallel to ``step_times``: True where the step paid a fresh
        #: XLA compile (warmup or retrace), so p99 can be reported with
        #: and without compilation stalls
        self.step_compiled: List[bool] = []
        #: tokens ingested as fresh context (full-sequence prefill plus
        #: prompt tails / fork continuations streamed through decode) --
        #: copy-on-write forks skip the shared prefix, so this is the
        #: number the prefix-sharing benches compare
        self.prefill_tokens = 0
        self._key = jax.random.PRNGKey(scfg.seed)
        # a byte budget sizes the page pool itself; n_pages is then unused
        self.pool = TieredStatePool(
            cfg, n_pages=None if scfg.byte_budget is not None else scfg.n_pages,
            n_slabs=(scfg.n_slabs if scfg.n_slabs is not None
                     else 2 * scfg.batch + 1),
            byte_budget=scfg.byte_budget,
            mesh_axes=mesh_axes, host_tier_bytes=scfg.host_tier_bytes,
            prefix_cache=scfg.prefix_cache,
            prefix_store_pages=scfg.prefix_store_pages)
        self.pool.attach_obs(self.obs)
        self.sched = Scheduler(scfg.scheduler)
        self.sched.obs = self.obs
        self.active: Dict[int, _Active] = {}
        self.rows: List[Optional[int]] = [None] * scfg.batch
        self.spilled: Dict[int, Tuple[SpilledRequest, List[int], int]] = {}
        #: finished-but-pinned requests: fork parents for sessions /
        #: N-way continuations; release_retained() frees them
        self.retained: Dict[int, _Active] = {}
        # account the block-table-native ops this engine actually dispatches
        self._traffic = _OpTrafficMeter(cfg, metrics=self.obs.metrics)
        self.preemptions = 0
        self._occ: List[float] = []
        self._frag: List[float] = []
        self._step_prefilled = 0          # full-sequence prefill tokens
                                          # of the step under way
        # --- resilience wiring (all None/empty => zero overhead) ---
        self.faults = FaultPlan.maybe(scfg.fault_plan, seed=scfg.seed)
        self.pool.faults = self.faults
        #: wall-clock step budget monitor (zero cost when
        #: ``step_budget_s`` is unset)
        self.watchdog = StepWatchdog(scfg.step_budget_s, obs=self.obs)
        self._nan_guard = (scfg.nan_guard if scfg.nan_guard is not None
                           else self.faults is not None)
        #: rid -> full replay token stream (prompt + generated) for the
        #: bounded re-prefill after a detected spill-blob corruption
        self._replay: Dict[int, List[int]] = {}
        self._reprefills: Dict[int, int] = {}
        #: rid -> consecutive failed admission attempts (degradation rung)
        self._admit_fails: Dict[int, int] = {}
        self._prefill_jit = self.obs.wrap_jit(
            _prefill_program(cfg, mesh_axes), "engine.prefill")
        #: bucketed prompts are prefilled padded, the padding masked
        self._masked = bool(scfg.prefill_buckets) and M.masked_prefill_ok(cfg)
        max_chunk_pages = pages_for(scfg.prefill_chunk)
        assert max_chunk_pages <= self.pool.usable_pages, \
            "prefill_chunk does not fit the page pool"
        # --- speculative decoding (serving/spec) ---
        self.draft = None
        self.kctl = None
        if scfg.spec is not None:
            from repro.serving.spec import (KController, ModelDraft,
                                            NGramDraft)
            assert scfg.spec_k >= 1, "spec_k must be at least 1"
            if scfg.spec == "ngram":
                self.draft = NGramDraft()
            elif scfg.spec.startswith("model:"):
                from repro.configs import get_smoke_config
                dcfg = get_smoke_config(scfg.spec.split(":", 1)[1]).with_(
                    state_quant=cfg.state_quant)
                # the draft pool is deliberately NOT obs-wrapped: its jits
                # are warmup-only per draft request and must not count
                # against the target engine's decode recompile budget
                self.draft = ModelDraft(
                    dcfg, max_requests=scfg.batch + 1, seed=scfg.seed)
            else:
                raise ValueError(
                    f"unknown spec draft source {scfg.spec!r} "
                    "(expected 'ngram' or 'model:<arch>')")
            self.kctl = KController(scfg.spec_k, window=scfg.spec_window)
            # per-position seeds inside the verify step are spec_seed + i,
            # so advance by n per step to keep the streams non-overlapping
            self._spec_seed = 0

    # ------------- lifecycle -------------

    def submit(self, req: Request):
        if req.parent_rid is not None and req.parent_rid not in self.retained:
            raise ValueError(
                f"fork parent {req.parent_rid} is not retained (submit the "
                "parent with retain=True and let it finish first)")
        req.t_submit = time.perf_counter()
        req.status = "queued"
        self.obs.metrics.counter("requests_submitted_total").inc()
        self.obs.lifecycle.enqueued(req.rid, t=req.t_submit)
        mq = self.scfg.max_queued
        if mq is not None and len(self.sched) >= mq:
            # overload shedding at the door: better an immediate, explicit
            # rejection than an unbounded queue nobody drains in time
            self.obs.metrics.counter("degradations_total", rung="shed").inc()
            self._finalize(req, "rejected",
                           detail=f"queue full (max_queued={mq})")
            return
        self.sched.push(req)

    def step(self) -> bool:
        """One engine step.  Its ``serve.step`` span holds a span for each
        part (admit, headroom, prefetch, then the decode step's prepare,
        dispatch, sync, account and commit) and, as args, the step number,
        the rows decoded, how many of them fed a prompt token
        (``tail_rows``), the tokens prefilled, the K/V positions one
        attention layer read (``kv_tokens``) and the K/V pages in use
        (``kv_pages``), and whether anything compiled.  Returns True while
        work remains, so callers can drive the engine open-loop
        (``while eng.step(): ...``) and interleave submits/aborts."""
        span = self.obs.span
        with span("serve.step", cat="step") as st:
            c0 = self.obs.recompiles.n_events
            self._step_prefilled = rows = tail_rows = 0
            self._step_kv = (0, 0)
            if self.faults is not None:
                self.faults.set_step(self.step_count)
            with span("serve.admit"):
                if self.scfg.request_timeout_s is not None:
                    self._expire_queued()
                admitted = self._admit()
            if self.active:
                with span("serve.headroom"):
                    self._ensure_headroom()
            if self.active:
                # stage prefetches *before* dispatching decode: the
                # host->device copies ride JAX's async dispatch behind the
                # decode kernels, so the next admission window's data lands
                # while this step runs
                with span("serve.prefetch"):
                    self._issue_prefetches()
                rows = len(self.active)
                tail_rows = sum(1 for a in self.active.values() if a.pending)
                self._decode_step()
            elif self.sched and not admitted:
                # queue non-empty but nothing fits and nothing runs: shed
                # the head loudly rather than spinning (a request whose
                # admission can *never* be satisfied would otherwise wedge
                # the engine)
                self._drop_queued(
                    self.sched.peek(), "rejected",
                    detail="cannot admit with the pool idle (request does "
                           "not fit the page budget)")
            st.set(step=self.step_count, rows=rows, tail_rows=tail_rows,
                   prefill_tokens=self._step_prefilled,
                   kv_tokens=self._step_kv[0], kv_pages=self._step_kv[1],
                   compiled=self.obs.recompiles.n_events > c0)
        return self.has_work()

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drain: step until queue + batch are empty; returns terminal
        requests.  If ``max_steps`` is hit first, still-active/queued
        requests are surfaced at the end of the returned list (statuses
        ``running``/``queued``) instead of being silently dropped; their
        lifecycle spans are closed with an explicit ``interrupted`` marker
        so traces never contain dangling spans (a later ``run()`` reopens
        the span if work resumes)."""
        for r in self.pending_requests():
            self.obs.lifecycle.reopen(r.rid)
        stalled = 0
        while self.has_work() and self.step_count < max_steps:
            before = (self.step_count, len(self.done))
            self.step()
            # no decode ran and nothing reached a terminal status: the
            # engine is wedged (e.g. a head-of-queue request admission can
            # never satisfy).  Bounded tolerance, then shed work loudly --
            # run() must terminate, never spin.
            stalled = 0 if (self.step_count, len(self.done)) != before \
                else stalled + 1
            if stalled >= 3:
                self._break_stall()
                stalled = 0
        if self.has_work():
            pending = self.pending_requests()
            for r in pending:
                self.obs.lifecycle.interrupt(r.rid)
            return self.done + pending
        self._sanitize_teardown()
        return self.done

    def _expire_queued(self) -> None:
        now = time.perf_counter()
        budget = self.scfg.request_timeout_s
        for req in self.sched.requests():
            if req.t_submit and now - req.t_submit > budget:
                self.obs.metrics.counter("request_timeouts_total").inc()
                self._drop_queued(
                    req, "rejected",
                    detail=f"queued longer than request_timeout_s={budget}")

    def _drop_queued(self, req: Request, status: str, detail: str) -> None:
        """Remove a not-yet-admitted request (queued or spilled) with full
        cleanup: scheduler entry, spill blob, staged prefetch, replay ctx."""
        rid = req.rid
        self.sched.remove(rid)
        if rid in self.spilled:
            sp, _, _ = self.spilled.pop(rid)
            self.pool.prefetch_cancel(rid)
            self.pool.drop_spilled(sp, rid)
        self._replay.pop(rid, None)
        self._admit_fails.pop(rid, None)
        self._finalize(req, status, detail=detail)

    def has_work(self) -> bool:
        return bool(self.sched) or bool(self.active)

    def pending_requests(self) -> List[Request]:
        """Requests submitted but not yet terminal (running, waiting, or
        spilled)."""
        return ([a.req for a in self.active.values()]
                + self.sched.requests())

    def abort(self, rid: int) -> bool:
        """Cancel a request at any lifecycle point: waiting, mid-decode, or
        spilled.  Frees its pages immediately; the request lands in
        ``done`` with status ``aborted`` (tokens already streamed remain in
        ``output``).  Returns False if ``rid`` is unknown or terminal."""
        if rid in self.active:
            a = self.active.pop(rid)
            self._free_row(rid)
            self._spec_release(rid)
            self.pool.release(rid)
            self._finalize(a.req, "aborted")
            return True
        if rid in self.spilled:
            sp, _, _ = self.spilled.pop(rid)
            self.pool.prefetch_cancel(rid)
            self.pool.drop_spilled(sp, rid)
            req = self.sched.remove(rid)
            assert req is not None, "spilled request must be in the heap"
            self._finalize(req, "aborted")
            return True
        req = self.sched.remove(rid)
        if req is not None:
            self._finalize(req, "aborted")
            return True
        return False

    # ------------- retained parents / copy-on-write fork -------------

    def retained_length(self, rid: int) -> int:
        return self.retained[rid].length

    def release_retained(self, rid: int):
        """Drop a retained parent's page references (shared pages free when
        the last fork drops; must not race a never-admitted fork child).
        Preempted fork children are fine: their spill blobs already hold
        their own references on the shared pages."""
        assert all(r.parent_rid != rid or r.rid in self.spilled
                   for r in self.sched.requests()), \
            f"retained {rid} still has unadmitted fork children"
        self.retained.pop(rid)
        self.pool.release(rid)

    # ------------- admission / preemption -------------

    def _admission_need(self, req: Request) -> int:
        """Pages admission must find free for ``req`` (plus one slab)."""
        if req.rid in self._replay:
            # corruption recovery re-prefills from the replay stream; the
            # prefix store is bypassed entirely
            return pages_for(
                self._prefill_lens(len(self._replay[req.rid]))[1])
        if req.rid in self.spilled:
            if self.pool.prefetch_ready(req.rid):
                return 0            # staged: commit is O(1) bookkeeping
            return self.spilled[req.rid][0].pages_needed
        if req.parent_rid is not None:
            # CoW fork: at most the private tail-page copy
            return 1 if self.retained[req.parent_rid].length % PAGE_TOKENS \
                else 0
        nodes = self.pool.prefix_match(req.prompt)
        if nodes:
            # prefix hit: promote any demoted nodes + one page of headroom
            # for the first streamed tail token
            return sum(1 for n in nodes if not n.resident) + 1
        n = len(req.prompt)
        return pages_for(max(min(n, self.scfg.prefill_chunk),
                             self._prefill_lens(n)[1]))

    def _admit(self) -> bool:
        admitted = False
        while len(self.active) < self.scfg.batch and self.sched:
            head = self.sched.peek()
            need = self._admission_need(head)
            if not self.pool.can_admit(need):
                # first try reclaiming device pages from the prefix store
                # (demote LRU nodes to host) before preempting live work
                self.pool.reclaim(need)
            if not self.pool.can_admit(need):
                victim = self.sched.choose_victim(
                    [a.req for a in self.active.values()])
                if victim is not None and self.sched.should_preempt(head,
                                                                    victim):
                    self._preempt(victim.rid)
                    continue
                break
            req = self.sched.pop()
            try:
                if req.rid in self.spilled:
                    ok = self._resume(req)
                elif req.parent_rid is not None:
                    ok = self._fork_into(req)
                else:
                    ok = self._prefill_into(req)
            except BlobCorruption:
                # the spill blob failed its checksum inside pool.resume:
                # the spilled entry is still intact -- recover by bounded
                # re-prefill (the request was popped, so re-push happens
                # inside the recovery)
                self._recover_corrupt(req, in_queue=False)
                continue
            if not ok:
                # transient allocation failure survived bounded retry:
                # walk the degradation ladder (progress is guaranteed --
                # the final rung sheds the request)
                self._degrade(req, need)
                continue
            self._admit_fails.pop(req.rid, None)
            admitted = True
        return admitted

    def _retry(self, site: str, fn) -> bool:
        """Bounded retry around an allocation-style pool call (the PL206
        contract: alloc/pin sites never assert success, they retry and
        escalate).  Counts retries and recoveries per site."""
        retried = [0]

        def on_retry(_k):
            retried[0] += 1
            self.obs.metrics.counter("fault_retries_total", site=site).inc()

        ok = bool(retry_transient(fn, on_retry=on_retry))
        if ok and retried[0]:
            self.obs.metrics.counter("faults_recovered_total",
                                     site=site).inc()
        return ok

    def _degrade(self, req: Request, need: int) -> None:
        """Admission of a popped request failed after bounded retry: walk
        the degradation ladder, escalating per request across attempts --
        reclaim store pages, then preempt live work, then shed the request
        with ``rejected``.  The rung counter guarantees termination."""
        fails = self._admit_fails.get(req.rid, 0) + 1
        self._admit_fails[req.rid] = fails
        m = self.obs.metrics
        if fails == 1:
            self.pool.reclaim(need + 1)
            m.counter("degradations_total", rung="demote_store").inc()
        elif fails == 2:
            victim = self.sched.choose_victim(
                [a.req for a in self.active.values()])
            if victim is not None:
                self._preempt(victim.rid)
            m.counter("degradations_total", rung="preempt").inc()
        else:
            m.counter("degradations_total", rung="shed").inc()
            self._drop_queued(
                req, "rejected",
                detail=f"admission failed after retries (need {need} pages)")
            return
        req.status = "queued"
        self.sched.push(req, resumed=True)

    def _recover_corrupt(self, req: Request, in_queue: bool) -> None:
        """A spill blob failed its checksum: drop the poisoned bytes and
        re-prefill the request from its retained token ids (prompt plus
        every token generated so far), bounded by ``REPREFILL_CAP``.

        ``in_queue`` distinguishes the two detection points: during a
        prefetch (request still in the scheduler heap, which must not be
        touched -- tombstoned rids cannot be re-pushed) vs during admission
        (request just popped, so recovery re-pushes it)."""
        rid = req.rid
        entry = self.spilled.pop(rid, None)
        self.pool.prefetch_cancel(rid)
        if entry is not None:
            self.pool.drop_spilled(entry[0], rid)
        self.obs.metrics.counter("blob_corruptions_total").inc()
        self.obs.tracer.instant("fault.blob_corrupt_detected", cat="fault",
                                track="engine", rid=rid)
        n = self._reprefills.get(rid, 0)
        if req.parent_rid is not None or n >= REPREFILL_CAP:
            # a fork child's shared prefix belongs to its parent -- its own
            # token ids cannot rebuild that state -- and a request that
            # keeps corrupting is dropped, not retried forever
            why = ("fork child spill blob corrupted (shared prefix is not "
                   "replayable)" if req.parent_rid is not None else
                   f"spill blob corrupted {n + 1}x (re-prefill cap "
                   f"{REPREFILL_CAP} exhausted)")
            if in_queue:
                self._drop_queued(req, "failed", detail=why)
            else:
                self._replay.pop(rid, None)
                self._finalize(req, "failed", detail=why)
            return
        self._reprefills[rid] = n + 1
        # everything the model had consumed, rebuilt through a fresh
        # prefill + streamed tail: the prompt plus all generated tokens
        self._replay[rid] = list(map(int, req.prompt)) + list(req.output)
        self.obs.metrics.counter("faults_recovered_total",
                                 site="blob_corrupt").inc()
        if not in_queue:
            req.status = "queued"
            self.sched.push(req, resumed=True)

    def _assign_row(self, rid: int):
        row = self.rows.index(None)
        self.rows[row] = rid
        if self.draft is not None and rid in self.active:
            # draft-side admission is best-effort: a refusal (draft pool
            # full) just means this request decodes without drafts for now
            self.draft.admit(rid, list(map(int, self.active[rid].req.prompt)))

    def _free_row(self, rid: int):
        self.rows[self.rows.index(rid)] = None

    def _spec_release(self, rid: int) -> None:
        """Drop every speculation-side trace of a terminal request: drafted-
        but-unverified tokens die with the draft state (they were never in
        ``req.output``), draft-model pages free, acceptance history resets."""
        if self.draft is not None:
            self.draft.release(rid)
        if self.kctl is not None:
            self.kctl.forget(rid)

    def _prefill_lens(self, n: int) -> Tuple[int, int]:
        """(real, padded): the tokens of an ``n``-token prompt that the
        full-sequence prefill takes, and the length it runs at.

        Unbucketed: ``min(n, prefill_chunk)``, unpadded -- one compiled
        prefill per distinct prompt length.  With ``prefill_buckets``, the
        smallest bucket up to ``prefill_chunk`` that covers them, the
        padding masked; where no bucket covers them, or the model cannot
        mask padding, the largest bucket that fits, unpadded (prompts
        shorter than every bucket keep their exact length).  The rest of
        the prompt streams through the decode batch."""
        s0 = min(n, self.scfg.prefill_chunk)
        buckets = self.scfg.prefill_buckets or ()
        if self._masked:
            cover = [b for b in buckets if s0 <= b <= self.scfg.prefill_chunk]
            if cover:
                return s0, min(cover)
        fits = [b for b in buckets if 0 < b <= s0]
        if fits:
            s0 = max(fits)
        return s0, s0

    def _prefill(self, params, batch):
        """The full-sequence prefill of ``batch["tokens"]`` (1, n), the
        exact tokens on the host: padded here to ``_prefill_lens(n)``'s
        length and run with ``lengths`` n when the engine masks padding.
        Returns the logits of the last real position and caches of the
        padded length.  The padding is numpy: a device op on the (1, n)
        tokens would compile once per distinct prompt length."""
        if not self._masked:
            return self._prefill_jit(params, batch=batch)
        tokens = np.asarray(batch["tokens"], np.int32)
        B, n = tokens.shape
        pad = self._prefill_lens(n)[1] - n
        return self._prefill_jit(
            params, batch={"tokens": np.pad(tokens, ((0, 0), (0, pad)))},
            lengths=np.full((B,), n, np.int32))

    def _prefill_into(self, req: Request) -> bool:
        replay = self._replay.get(req.rid)
        if replay is None:
            nodes = self.pool.prefix_match(req.prompt)
            if nodes:
                if self.pool.prefix_admit(req.rid, nodes):
                    self._prefix_hit_into(req, nodes)
                    return True
                # ladder rung "drop_prefix": the store hit could not be
                # admitted (promotion short) -- fall back to plain prefill
                self.obs.metrics.counter("degradations_total",
                                         rung="drop_prefix").inc()
            self.pool.note_prefix_miss()
        src = np.asarray(replay, np.int32) if replay is not None \
            else req.prompt
        self.obs.lifecycle.phase(req.rid, "prefill")
        s0, padded = self._prefill_lens(len(src))
        if not self._retry("alloc", lambda: self.pool.register(
                req.rid, pages_for(padded))):
            return False                # replay ctx (if any) stays for retry
        self._replay.pop(req.rid, None)
        # the whole prompt is fresh context: s0 through full-sequence
        # prefill (run at ``padded``), the tail streamed through the decode
        # batch.  With prefill_buckets set, ``padded`` comes from a fixed
        # bucket set, so the prefill runs a bounded family of shapes.
        self._count_prefill(len(src))
        self._step_prefilled += s0
        a = _Active(req, length=s0, pending=list(map(int, src[s0:])),
                    cur_token=-1, replayed=replay is not None)
        m = self.obs.metrics
        m.counter("prefill_pad_tokens_total").inc(padded - s0)
        m.counter("prefill_tail_tokens_total").inc(len(a.pending))
        with self.obs.span("serve.prefill", cat="prefill", rid=req.rid,
                           tokens=s0, padded=padded, tail=len(a.pending),
                           replay=replay is not None):
            # host tokens, any length: _prefill pads them to a bucket
            prompt = np.asarray(src[:s0], np.int32)[None]  # lint: disable=JH103
            logits, row_caches = self._prefill(
                self.params, batch={"tokens": prompt, "targets": prompt})
            self.pool.insert_prefill(req.rid, row_caches)
            if replay is None and s0 == padded and s0 % PAGE_TOKENS == 0:
                # the prefilled pages are full and immutable: remember them
                # in the prefix store for future requests sharing this
                # prompt (replay streams contain generated tokens -- never
                # stored)
                self.pool.store_insert(req.rid, req.prompt[:s0])
            if not a.pending:
                self._key, toks = _sample_tokens(self._key, logits,
                                                 self.scfg.sampling)
                tok = int(toks[0])
                if not req.t_first:
                    req.t_first = time.perf_counter()
                    self.obs.lifecycle.first_token(req.rid, t=req.t_first)
                req.output.append(tok)
                a.cur_token = tok
        self._running(a)
        if req.output and (len(req.output) >= req.max_new_tokens
                           or (req.eos_id is not None
                               and req.output[-1] == req.eos_id)):
            self._finish(req.rid)       # prefill already produced the end
        return True

    def _prefix_hit_into(self, req: Request, nodes) -> None:
        """Admit a request whose prompt prefix came out of the radix store:
        the stored pages joined its block table by reference inside
        ``prefix_admit`` (no prefill compute for them), the tail node's
        recurrent-state snapshot seeded its slab, and only the *un-cached*
        prompt tail streams through the decode batch -- the cross-request
        twin of ``_fork_into``."""
        j = len(nodes)
        length = j * PAGE_TOKENS
        self.obs.lifecycle.phase(req.rid, "prefill")
        pending = list(map(int, req.prompt[length:]))
        assert pending, "prefix match must leave a prompt tail"
        # only the un-cached tail is fresh context -- that is the whole point
        self._count_prefill(len(pending))
        self._running(_Active(req, length=length, pending=pending,
                              cur_token=-1))

    def _running(self, a: _Active) -> None:
        """Put an admitted request into the decode batch: it ingests what
        is left of its prompt until its first token (the lifecycle's
        ``ingest`` phase), then decodes."""
        rid = a.req.rid
        self.active[rid] = a
        self._assign_row(rid)
        a.req.status = "running"
        self.obs.lifecycle.phase(
            rid, "ingest" if a.pending and not a.req.t_first else "decode")

    def _issue_prefetches(self) -> None:
        """Scheduler-lookahead prefetch: for requests in the next admission
        window, dispatch spilled-blob copies into staging pages and promote
        demoted prefix-store nodes *now*, so the copies overlap the decode
        step dispatched right after and their eventual admission is O(1)."""
        window = self.scfg.prefetch_window
        if window <= 0:
            return
        reserve = max(1, len(self.active))
        for req in self.sched.lookahead(window):
            if req.rid in self.spilled:
                try:
                    self.pool.prefetch_begin(req.rid,
                                             self.spilled[req.rid][0],
                                             reserve=reserve)
                except BlobCorruption:
                    # detected before the device copy was ever dispatched;
                    # the request stays in the scheduler heap and its next
                    # admission re-prefills from the replay stream
                    self._recover_corrupt(req, in_queue=True)
            elif req.parent_rid is None and req.rid not in self._replay:
                self.pool.prefetch_prefix(req.prompt)

    def _fork_into(self, req: Request) -> bool:
        """Admit a copy-on-write fork: share the retained parent's full
        prefix pages, copy only its partial tail page + slab, and stream
        the continuation tokens (the parent's final sampled token, then the
        new turn's tokens) through the decode batch -- no re-prefill of the
        shared prefix ever happens."""
        parent = self.retained.get(req.parent_rid)
        assert parent is not None, f"fork parent {req.parent_rid} released"
        if not self._retry("alloc", lambda: self.pool.fork(
                req.parent_rid, req.rid, parent.length)):
            return False
        pending = [int(parent.cur_token)] + list(map(int, req.prompt))
        self._count_prefill(len(pending))
        self._running(_Active(req, length=parent.length, pending=pending,
                              cur_token=-1))
        return True

    def _resume(self, req: Request) -> bool:
        # read without popping: a checksum failure inside ``pool.resume``
        # propagates as BlobCorruption with the spill entry intact, so the
        # recovery path can account for and drop the poisoned blob
        sp, pending, cur = self.spilled[req.rid]
        if not self._retry("alloc",
                           lambda: self.pool.resume(req.rid, sp)):
            return False
        del self.spilled[req.rid]
        self._running(_Active(req, sp.length, pending, cur))
        return True

    def _preempt(self, rid: int):
        """Evict by page spill: state leaves the device bit-exactly and the
        request goes back to the scheduler queue."""
        a = self.active.pop(rid)
        self._free_row(rid)
        if self.draft is not None:
            self.draft.suspend(rid)
        sp = self.pool.spill(rid, a.length)
        self.spilled[rid] = (sp, a.pending, a.cur_token)
        a.req.status = "queued"
        self.obs.lifecycle.phase(rid, "spilled")
        self.obs.metrics.counter("preemptions_total").inc()
        self.sched.push(a.req, resumed=True)
        self.preemptions += 1

    def _finish(self, rid: int, truncated: bool = False):
        a = self.active.pop(rid)
        self._free_row(rid)
        self._spec_release(rid)
        if a.req.retain and not truncated:
            # keep the pages pinned: this request is now a fork parent
            self.retained[rid] = a
        else:
            self.pool.release(rid)
        self._finalize(a.req, "truncated" if truncated else "done")

    def _ensure_headroom(self):
        """Every active request must own the page its next token writes --
        and with speculation on, every page an *accepted* draft could write:
        a generation row may commit up to ``spec_k + 1`` tokens per step,
        none of which may land on the shared scratch page."""
        for rid in list(self.active):
            a = self.active.get(rid)
            if a is None:
                continue
            span = (self.scfg.spec_k
                    if self.draft is not None and not a.pending else 0)
            needed = (a.length + span) // PAGE_TOKENS + 1
            while needed > len(self.pool.page_table[rid]):
                short = needed - len(self.pool.page_table[rid])
                if self._retry("alloc",
                               lambda: self.pool.grow(rid, short)):
                    break
                victim = self.sched.choose_victim(
                    [b.req for b in self.active.values()], exclude=a.req)
                if victim is None:
                    self._finish(rid, truncated=True)
                    break
                self._preempt(victim.rid)

    # ------------- the decode step -------------

    def _decode_step(self):
        if self.draft is not None:
            self._spec_decode_step()
            return
        self.step_count += 1
        span = self.obs.span
        with span("serve.prepare"):
            B = self.scfg.batch
            tokens = np.zeros((B,), np.int32)
            lengths = np.zeros((B,), np.int32)
            for row, rid in enumerate(self.rows):
                if rid is None:
                    continue
                a = self.active[rid]
                tokens[row] = a.pending[0] if a.pending else a.cur_token
                lengths[row] = a.length
        c0 = self.obs.recompiles.n_events
        t0 = time.perf_counter()
        self._maybe_stall()
        # the pool's own serve.prepare (block table, slab ids, device
        # arrays) and serve.dispatch (the jitted step) spans sit in here
        logits = self.pool.decode(self.params, self.rows, tokens, lengths,
                                  seed=self.step_count)
        with span("serve.dispatch"):
            if self.faults is not None:
                logits = self._inject_nan(logits)
            self._key, toks = _sample_tokens(self._key, logits,
                                             self.scfg.sampling)
        with span("serve.sync"):
            toks_np = np.asarray(toks)
            bad_rows = self._scan_nonfinite(logits) if self._nan_guard \
                else ()
        self._record_step(time.perf_counter() - t0,
                          compiled=self.obs.recompiles.n_events > c0)
        self._account(lengths, 1)

        with span("serve.commit"):
            for row, rid in enumerate(self.rows):
                if rid is None:
                    continue
                if row in bad_rows:
                    # quarantine exactly this request -- its logits are
                    # non-finite and its sampled token is garbage.  Every
                    # other row's token stream is untouched (sampling is
                    # row-wise).
                    self._fail_active(rid,
                                      "non-finite logits after decode step")
                    continue
                a = self.active[rid]
                a.length += 1
                self._store_prompt_page(rid, a)
                if a.pending:
                    fed = a.pending.pop(0)
                    a.cur_token = fed
                    if a.pending:
                        continue            # still consuming the prompt
                    # that was the last prompt token: this step's logits
                    # are the first-generation distribution
                    tok = int(toks_np[row])
                    if not a.req.t_first:   # replays already emitted tokens
                        a.req.t_first = time.perf_counter()
                        self.obs.lifecycle.first_token(rid, t=a.req.t_first)
                    a.req.output.append(tok)
                    a.cur_token = tok
                else:
                    tok = int(toks_np[row])
                    a.req.output.append(tok)
                    a.cur_token = tok
                req = a.req
                hit_eos = (req.eos_id is not None and req.output
                           and req.output[-1] == req.eos_id)
                if len(req.output) >= req.max_new_tokens or hit_eos:
                    self._finish(rid)

    def _maybe_stall(self) -> None:
        """A planted ``slow_step`` fault: sleep inside the timed part of the
        step, where the watchdog must see (and flag) the blown budget."""
        if self.faults is not None and self.faults.should_fire("slow_step"):
            stall_s = self.faults.param("slow_step", "ms") / 1000.0
            self.obs.metrics.counter("faults_injected_total",
                                     site="slow_step").inc()
            self.obs.tracer.instant("fault.slow_step", cat="fault",
                                    track="engine", ms=stall_s * 1e3)
            time.sleep(stall_s)

    def _store_prompt_page(self, rid: int, a: _Active) -> None:
        """A chunk-streamed prompt that just filled a page: the page is
        immutable from here on and the slab holds the recurrent state at
        this exact boundary -- store both in the prefix store."""
        if (a.req.parent_rid is None and not a.replayed
                and a.length % PAGE_TOKENS == 0
                and a.length <= len(a.req.prompt)):
            self.pool.store_insert(rid, a.req.prompt[:a.length])

    def _account(self, lengths: np.ndarray, n: int) -> None:
        """The step's ``serve.account`` span: op traffic at the pages each
        row attended (``length + n`` positions), pool occupancy and
        fragmentation.  Copy-on-write shared pages are deduplicated across
        rows -- a physical page streamed for several forks of one prefix is
        attributed once.  It also sets the step's ``kv_tokens`` (positions
        one attention layer reads, a shared page counted once at the most
        any row reads of it) and ``kv_pages`` (pages in use); both are 0 for
        a model without K/V."""
        with self.obs.span("serve.account"):
            seen_pages = set()
            units = []
            rids = []
            read = {}                   # page id -> tokens read from it
            for row, rid in enumerate(self.rows):
                if rid is None:
                    continue
                rids.append(rid)
                table = self.pool.page_table[rid]
                total = int(lengths[row]) + n
                npg = min(pages_for(total), len(table))
                fresh = [p for p in table[:npg] if p not in seen_pages]
                seen_pages.update(fresh)
                units.append(max(len(fresh), 1))
                for j, p in enumerate(table[:npg]):
                    read[p] = max(read.get(p, 0),
                                  min(PAGE_TOKENS, total - j * PAGE_TOKENS))
            if self.pool.page_nbytes > 0:
                self._step_kv = (sum(read.values()),
                                 self.pool.usable_pages - self.pool.free_pages)
            self._traffic.account_units(units)
            self._occ.append(self.pool.occupancy())
            self._frag.append(self.pool.fragmentation(
                {r: self.active[r].length for r in rids}))
            self.obs.tracer.counter(
                "pool", {"occupancy": self._occ[-1],
                         "fragmentation": self._frag[-1]})

    # ------------- the speculative decode step -------------

    def _spec_decode_step(self):
        """One continuous-batching step with speculative verification.

        Every active row rides the same fused ``spec_verify`` pass at the
        fixed compiled width ``n = spec_k + 1`` (so the recompile watcher
        stays at the warmup count): generation rows carry their current
        token plus up to ``k`` drafted continuations, prompt-streaming rows
        carry one real position padded with garbage.  Afterwards the model
        state is rolled back per row to exactly the accepted prefix
        (``commit_spec``), which also unwinds the garbage positions the
        padding pushed through the recurrent state.

        Greedy rows emit the model's own argmax stream -- drafts only decide
        how many of those tokens one pass may confirm -- so greedy output is
        bit-identical to non-speculative decoding.  Sampled rows use
        rejection sampling against :func:`filtered_probs`, which preserves
        the non-speculative sampling distribution.
        """
        self.step_count += 1
        span = self.obs.span
        B = self.scfg.batch
        n = self.scfg.spec_k + 1
        with span("serve.prepare"):
            tokens = np.zeros((B, n), np.int32)
            lengths = np.zeros((B,), np.int32)
            drafts: Dict[int, List[int]] = {}
            for row, rid in enumerate(self.rows):
                if rid is None:
                    continue
                a = self.active[rid]
                lengths[row] = a.length
                if a.pending:
                    tokens[row, 0] = a.pending[0]  # positions 1.. garbage
                    continue
                # the budget keeps one fully-accepted step inside the
                # request's remaining token allowance, so emitted tokens
                # never need a post-hoc cut that would desync length from
                # committed state
                budget = min(self.kctl.k_for(rid), self.scfg.spec_k,
                             a.req.max_new_tokens - len(a.req.output) - 1)
                d = []
                if budget > 0:
                    ctx = list(map(int, a.req.prompt)) + list(a.req.output)
                    d = [int(t) for t in
                         self.draft.propose(rid, ctx, budget)[:budget]]
                drafts[rid] = d
                tokens[row, 0] = a.cur_token
                tokens[row, 1:1 + len(d)] = d
            # every row's block table must span the garbage positions too,
            # or an out-of-width page index would clamp onto a live
            # physical page
            min_pages = max(pages_for(int(lengths[row]) + n)
                            for row, rid in enumerate(self.rows)
                            if rid is not None)
        c0 = self.obs.recompiles.n_events
        t0 = time.perf_counter()
        self._maybe_stall()
        seed = self._spec_seed
        self._spec_seed += n
        logits, snaps = self.pool.decode_spec(
            self.params, self.rows, tokens, lengths, seed=seed,
            min_pages=min_pages)
        with span("serve.sync"):
            if self.faults is not None:
                logits = self._inject_nan(logits)
            bad_rows = self._scan_nonfinite(logits) if self._nan_guard \
                else ()
            greedy = self.scfg.sampling.temperature <= 0.0
            if greedy:
                # same device op as the sampler's greedy branch, so ties
                # break identically to non-speculative decoding
                g = np.asarray(jnp.argmax(logits, axis=-1))
            else:
                probs = np.asarray(filtered_probs(logits, self.scfg.sampling))
        with span("serve.commit"):
            sel = np.zeros((B,), np.int32)
            emits: Dict[int, List[int]] = {}
            for row, rid in enumerate(self.rows):
                if rid is None or row in bad_rows:
                    continue
                a = self.active[rid]
                if a.pending:
                    continue                  # single real position: sel = 0
                d = drafts.get(rid, [])
                if greedy:
                    m = 0
                    while m < len(d) and d[m] == int(g[row, m]):
                        m += 1
                    emit = [int(g[row, j]) for j in range(m + 1)]
                else:
                    rng = np.random.default_rng(
                        (self.scfg.seed, self.step_count, row))
                    emit = []
                    for j, t in enumerate(d):
                        pj = probs[row, j]
                        pj = pj / pj.sum()
                        if rng.random() < pj[t]:
                            emit.append(t)    # accepted with probability p(t)
                            continue
                        # rejected: the correction comes from the residual
                        # distribution max(0, p - q) with the one-hot draft q
                        q = pj.copy()
                        q[t] = 0.0
                        s = q.sum()
                        if s <= 0.0:
                            emit.append(t)    # p was a point mass on t
                            continue
                        emit.append(int(rng.choice(len(q), p=q / s)))
                        break
                    else:
                        pj = probs[row, len(d)]
                        emit.append(int(rng.choice(len(pj), p=pj / pj.sum())))
                if a.req.eos_id is not None and a.req.eos_id in emit:
                    emit = emit[:emit.index(a.req.eos_id) + 1]
                sel[row] = len(emit) - 1
                emits[rid] = emit
            # roll state back to the accepted prefix *before* any host-side
            # bookkeeping -- every row (prompt rows included: their garbage
            # padding advanced recurrent state too) needs its slab restored
            self.pool.commit_spec(self.rows, snaps, sel)
        self._record_step(time.perf_counter() - t0,
                          compiled=self.obs.recompiles.n_events > c0)
        # one cache stream serves the whole verify span: account the pages
        # attended at length + n once, amortized over the accepted tokens
        self._account(lengths, n)
        with span("serve.commit"):
            n_proposed = n_accepted = n_steps = 0
            for row, rid in enumerate(self.rows):
                if rid is None:
                    continue
                if row in bad_rows:
                    self._fail_active(rid,
                                      "non-finite logits after decode step")
                    continue
                a = self.active[rid]
                if a.pending:
                    a.length += 1
                    self._store_prompt_page(rid, a)
                    fed = a.pending.pop(0)
                    a.cur_token = fed
                    if a.pending:
                        continue
                    tok = (int(g[row, 0]) if greedy else int(
                        np.random.default_rng(
                            (self.scfg.seed, self.step_count, row)
                        ).choice(probs.shape[-1],
                                 p=probs[row, 0] / probs[row, 0].sum())))
                    if not a.req.t_first:
                        a.req.t_first = time.perf_counter()
                        self.obs.lifecycle.first_token(rid, t=a.req.t_first)
                    a.req.output.append(tok)
                    a.cur_token = tok
                else:
                    emit = emits[rid]
                    proposed = len(drafts.get(rid, []))
                    # the last emitted token is the model's own (correction
                    # or bonus), so drafts surviving into the stream are
                    # len - 1, capped by proposed (an eos cut can only
                    # shorten the prefix)
                    accepted = min(len(emit) - 1, proposed)
                    self.kctl.observe(rid, proposed, accepted)
                    n_proposed += proposed
                    n_accepted += accepted
                    n_steps += 1
                    a.length += len(emit)
                    if not a.req.t_first:
                        a.req.t_first = time.perf_counter()
                        self.obs.lifecycle.first_token(rid, t=a.req.t_first)
                    a.req.output.extend(emit)
                    a.cur_token = emit[-1]
                req = a.req
                hit_eos = (req.eos_id is not None and req.output
                           and req.output[-1] == req.eos_id)
                if len(req.output) >= req.max_new_tokens or hit_eos:
                    self._finish(rid)
            m = self.obs.metrics
            m.counter("spec_proposed_tokens_total").inc(n_proposed)
            m.counter("spec_accepted_tokens_total").inc(n_accepted)
            m.counter("spec_verify_steps_total").inc(n_steps)
            if n_steps:
                self.obs.tracer.counter(
                    "spec", {"proposed": n_proposed, "accepted": n_accepted})

    # ------------- fault handling -------------

    def _inject_nan(self, logits):
        """Apply any scheduled ``nan`` faults: poison the logits row of the
        targeted request (the guard below must quarantine it)."""
        for row, rid in enumerate(self.rows):
            if rid is not None and self.faults.should_fire("nan", rid=rid):
                logits = logits.at[row].set(jnp.nan)
                self.obs.metrics.counter("faults_injected_total",
                                         site="nan").inc()
                self.obs.tracer.instant("fault.nan", cat="fault",
                                        track="engine", rid=rid, row=row)
        return logits

    def _scan_nonfinite(self, logits) -> set:
        """Rows whose logits contain NaN/Inf (one device sync; only runs
        when the guard is enabled).  Reduces over every non-batch axis so
        the (B, V) plain decode and (B, n, V) speculative verify shapes both
        collapse to one flag per row."""
        axes = tuple(range(1, logits.ndim))
        finite = np.asarray(jnp.all(jnp.isfinite(logits), axis=axes))
        return {row for row, rid in enumerate(self.rows)
                if rid is not None and not bool(finite[row])}

    def _fail_active(self, rid: int, reason: str) -> None:
        """Quarantine one active request mid-batch: free its row and pages
        immediately, close its lifecycle span as ``failed``.  The rest of
        the batch keeps decoding bit-exactly."""
        a = self.active.pop(rid)
        self._free_row(rid)
        self._spec_release(rid)
        self.pool.release(rid)
        self.obs.metrics.counter("quarantines_total").inc()
        self.obs.tracer.instant("fault.quarantine", cat="fault",
                                track="engine", rid=rid)
        self._finalize(a.req, "failed", detail=reason)

    def _break_stall(self) -> None:
        """No-progress steps in ``run()``: shed the unadmittable queue head
        with a clear reason instead of spinning forever."""
        if not self.sched:
            return            # every queued request is in the scheduler
        head = self.sched.peek()
        self.obs.metrics.counter("stalls_broken_total").inc()
        self._drop_queued(
            head, "rejected",
            detail="engine made no progress for 3 consecutive steps with "
                   "this request at the head of the queue")

    def _sanitize_teardown(self) -> None:
        # only assert once the spill set is empty: engine-held
        # SpilledRequest objects legitimately own shared pages mid-flight
        if not self.spilled:
            self.pool.sanitizer_check_leaks(
                what=f"drained paged engine (step {self.step_count})")
            if self.draft is not None and hasattr(
                    self.draft, "sanitizer_check_leaks"):
                self.draft.sanitizer_check_leaks(
                    what=f"drained draft pool (step {self.step_count})")

    # ------------- bookkeeping -------------

    def _finalize(self, req: Request, status: str,
                  detail: Optional[str] = None):
        req.status = status
        if detail is not None:
            req.detail = detail
        req.truncated = status == "truncated"
        req.t_done = time.perf_counter()
        self.done.append(req)
        m = self.obs.metrics
        m.counter("requests_total", status=status).inc()
        m.counter("tokens_total").inc(len(req.output))
        self.obs.lifecycle.finish(req.rid, status,
                                  n_tokens=len(req.output), t=req.t_done)

    def _count_prefill(self, n: int):
        """Fresh-context tokens ingested (prefill + streamed tails)."""
        self.prefill_tokens += int(n)
        self.obs.metrics.counter("prefill_tokens_total").inc(int(n))

    def _record_step(self, dt: float, compiled: bool):
        """Per-step bookkeeping: the step-time series with its compile tag
        and the ``step_s`` histogram split by tag (the step's trace event
        is its ``serve.step`` span)."""
        self.step_times.append(dt)
        self.step_compiled.append(compiled)
        self.watchdog.observe(self.step_count, dt)
        self.obs.metrics.histogram(
            "step_s", compile="true" if compiled else "false").observe(dt)

    # ------------- stats -------------

    def stats(self) -> Dict[str, float]:
        """Always the full key schema -- zeros before anything finishes.

        The dict is a *view over the obs metrics registry*: counts read
        the counters the lifecycle hooks incremented, percentiles read the
        registry histograms (``ttft_s``, ``step_s`` split by compile tag,
        ``tok_latency_s``).  Step latency is additionally reported with
        compile steps excluded (``*_step_nocompile_s``) so steady-state
        latency separates from compilation stalls, and ``recompiles``
        counts every fresh XLA trace the watcher saw.
        """
        m = self.obs.metrics
        pending = self.pending_requests()
        n_active = sum(1 for r in pending if r.status == "running")
        n_queued = sum(1 for r in pending if r.status == "queued")
        m.gauge("active_requests").set(n_active)
        m.gauge("queued_requests").set(n_queued)
        out: Dict[str, float] = {
            "tokens": m.value("tokens_total"),
            "wall_s": 0.0, "tokens_per_s": 0.0,
            "prefill_tokens": m.value("prefill_tokens_total"),
            "requests_done": m.value("requests_total", status="done"),
            "requests_aborted": m.value("requests_total", status="aborted"),
            "requests_truncated": m.value("requests_total",
                                          status="truncated"),
            "requests_failed": m.value("requests_total", status="failed"),
            "requests_rejected": m.value("requests_total",
                                         status="rejected"),
            "active_requests": float(n_active),
            "queued_requests": float(n_queued),
        }
        timed = [r for r in self.done if r.t_done > 0]
        if timed:
            t0 = min(r.t_submit for r in timed)
            t1 = max(r.t_done for r in timed)
            out["wall_s"] = t1 - t0
            out["tokens_per_s"] = out["tokens"] / max(t1 - t0, 1e-9)
        ttft = m.histogram("ttft_s")
        out["mean_ttft_s"] = ttft.mean
        out["p50_ttft_s"] = ttft.percentile(50)
        out["p99_ttft_s"] = ttft.percentile(99)
        steps_all = m.family_samples("step_s")
        out["p50_step_s"] = (float(np.percentile(steps_all, 50))
                             if steps_all else 0.0)
        out["p99_step_s"] = (float(np.percentile(steps_all, 99))
                             if steps_all else 0.0)
        steady = m.histogram("step_s", compile="false")
        out["p50_step_nocompile_s"] = steady.percentile(50)
        out["p99_step_nocompile_s"] = steady.percentile(99)
        out["compile_steps"] = float(
            m.histogram("step_s", compile="true").count)
        tok = m.histogram("tok_latency_s")
        out["p50_tok_latency_s"] = tok.percentile(50)
        out["p99_tok_latency_s"] = tok.percentile(99)
        out["recompiles"] = float(self.obs.recompiles.n_events)
        # speculation accounting is schema-stable: zeros when speculation is
        # off so downstream consumers never key-miss
        proposed = m.value("spec_proposed_tokens_total")
        accepted = m.value("spec_accepted_tokens_total")
        steps = m.value("spec_verify_steps_total")
        out["proposed_tokens"] = proposed
        out["accepted_tokens"] = accepted
        out["acceptance_rate"] = accepted / proposed if proposed else 0.0
        # each verify row-step emits the accepted drafts plus one token the
        # target model produced itself, so the floor is 1.0, not 0.0
        out["accepted_tokens_per_step"] = ((accepted + steps) / steps
                                           if steps else 0.0)
        out.update(self._traffic.stats())
        out.update({

            "preemptions": float(self.preemptions),
            "occupancy": float(np.mean(self._occ)) if self._occ else 0.0,
            "fragmentation": (float(np.mean(self._frag))
                              if self._frag else 0.0),
            # bytes still moved by gather/scatter: spill/resume, prefill
            # insertion, and the one-page fork copy -- the decode loop
            # contributes zero
            "gather_bytes": float(self.pool.gather_bytes),
            "pages_allocated": float(self.pool.pages_allocated),
            "shared_page_hits": float(self.pool.shared_page_hits),
            # peak, not instantaneous: sharing savings survive request
            # release in end-of-run stats (the live value is also exposed)
            "shared_page_savings": float(self.pool.shared_savings_peak),
            "shared_page_savings_live": float(self.pool.shared_page_savings),
            # --- bucketed prefill: padding run, prompt left to decode ---
            "prefill_pad_tokens": self.obs.metrics.value(
                "prefill_pad_tokens_total"),
            "prefill_tail_tokens": self.obs.metrics.value(
                "prefill_tail_tokens_total"),
            # --- tiered memory hierarchy ---
            "prefix_hits": float(self.pool.prefix_hits),
            "prefix_hit_pages": float(self.pool.prefix_hit_pages),
            "prefix_hit_tokens": float(self.pool.prefix_hit_tokens),
            "prefix_store_pages": float(
                self.pool.store.n_pages if self.pool.store else 0),
            "prefetch_commits": float(self.pool.prefetch_commits),
            "tier_hits": self.obs.metrics.family_total("tier_hit_total"),
            "tier_misses": self.obs.metrics.family_total("tier_miss_total"),
            "promote_bytes": self.obs.metrics.family_total(
                "promote_bytes_total"),
            "demote_bytes": self.obs.metrics.family_total(
                "demote_bytes_total"),
            "host_bytes": float(self.pool.host.bytes_used),
        })
        return out

    def bank_report(self) -> Dict[str, float]:
        """Score the pool's *actual* page map with the PIM timing model:
        the bank traffic of one decode step over the requests now active,
        computed on demand (no decode step accounts it)."""
        from repro.core import pimsim
        m = self.pool.bank_traffic(list(self.active))
        rep = pimsim.placement_step_latency(m, pimsim.SystemConfig())
        rep["imbalance"] = self.pool.placement.imbalance()
        return rep
