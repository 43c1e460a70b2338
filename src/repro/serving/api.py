"""Request-lifecycle serving API: one streaming ``Engine`` facade.

The batch-offline engines (``submit()`` everything, ``run()`` to drain,
read ``done`` at the end) become a request-lifecycle API shaped like a real
serving front-end:

  * one ``ServeConfig`` configures the paged engine
    (:class:`repro.serving.engine.PagedServingEngine`);
  * ``Engine.submit()`` returns a ``RequestHandle`` that streams tokens as
    they are sampled each ``step()``, exposes the terminal status
    (``done`` / ``aborted`` / ``truncated``) and can ``abort()`` mid-decode
    (pages free immediately, spilled victims included);
  * ``Engine.step()`` is the explicit event loop -- drive it open-loop,
    interleaving submits/aborts between steps; ``run()`` stays as the
    drain-to-empty wrapper;
  * ``Engine.fork()`` starts a continuation of a retained
    parent via copy-on-write prefix sharing: the child references the
    parent's full prefix pages and copies only the partial tail page, so N
    sampled continuations of one prompt or the next turn of a chat skip
    re-prefilling the shared context entirely.  ``Session`` wraps that into
    multi-turn chat;
  * ``ServeConfig(prefix_cache=True)`` makes that sharing
    *automatic and cross-request*: a radix prefix store remembers every
    full prompt page served, and any later ``submit()`` whose prompt shares
    the prefix adopts the stored pages -- no explicit ``fork()``.  Stored
    pages outlive their request under ``prefix_store_pages`` (LRU), can be
    demoted to a ``host_tier_bytes``-budgeted host tier, and come back via
    scheduler-lookahead async prefetch (see ``serving/memory/tiered``).

    eng = Engine(params, cfg, ServeConfig())
    h = eng.submit(prompt, max_new_tokens=32)
    for tok in h:                      # drives eng.step() under the hood
        print(tok)

    chat = eng.session()
    first = chat.send(user_turn_1).result()
    reply = chat.send(user_turn_2)     # forks -- no re-prefill of turn 1
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.models.config import ModelConfig
from repro.obs import Observability, RequestRecord
from repro.serving.engine import (PagedServingEngine, Request,
                                  TERMINAL_STATUSES)
from repro.serving.sampler import SamplingConfig
from repro.serving.scheduler import SchedulerConfig

__all__ = ["ServeConfig", "Engine", "RequestHandle", "Session", "Request"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The serving config, read by :class:`PagedServingEngine`: the paged,
    bank-aware state/KV pool, the preempting scheduler, chunked prefill and
    copy-on-write prefix sharing / sessions."""
    backend: str = "paged"             # the only engine; any other value
                                       # is refused
    batch: int = 4                     # rows in the jitted decode step
    n_pages: Optional[int] = 33        # pool pages (incl. 1 scratch)
    n_slabs: Optional[int] = None      # state slabs (default 2*batch+1)
    byte_budget: Optional[int] = None  # sizes the pool instead of n_pages
    prefill_chunk: int = 128           # longest full-sequence prefill; the
                                       # prompt tail streams through decode
    # opt-in prefill length bucketing (JH103): when set, the prefill jit
    # compiles one executable per *bucket* instead of one per distinct
    # prompt length.  A prompt that a bucket up to prefill_chunk covers is
    # prefilled whole at the smallest such bucket, the padding masked
    # (where the model's layers can mask it: M.masked_prefill_ok); a longer
    # one prefills the largest bucket that fits and streams the rest
    # through the decode batch.  Off by default: streamed tokens take
    # other stochastic-rounding draws than prefilled ones, so mx8 token
    # streams differ from the unbucketed engine (both are valid).
    prefill_buckets: Optional[Tuple[int, ...]] = None
    sampling: SamplingConfig = SamplingConfig()
    scheduler: SchedulerConfig = SchedulerConfig()
    seed: int = 0
    # --- tiered memory hierarchy (serving/memory/tiered) ---
    prefix_cache: bool = False         # radix prefix store: requests that
                                       # share a prompt prefix with earlier
                                       # requests adopt its pages, no fork()
    prefix_store_pages: int = 64       # store capacity in pages (LRU)
    host_tier_bytes: Optional[int] = None  # host DRAM budget (None = off)
    prefetch_window: int = 2           # scheduler lookahead for async
                                       # spill-resume / prefix prefetch
    # --- resilience / fault injection (serving/faults, resilience) ---
    fault_plan: Optional[str] = None   # fault spec string (see
                                       # serving/faults); REPRO_FAULTS env
                                       # applies when unset
    nan_guard: Optional[bool] = None   # post-step non-finite-logits guard
                                       # (None = on iff faults active; the
                                       # check costs one device sync)
    max_queued: Optional[int] = None   # admission control: queue-depth cap,
                                       # excess submits end ``rejected``
    request_timeout_s: Optional[float] = None  # max queue wait -> rejected
    step_budget_s: Optional[float] = None      # watchdog wall-clock budget
    # --- speculative decoding (serving/spec) ---
    spec: Optional[str] = None         # draft source: "ngram" (self-draft)
                                       # or "model:<arch>" (small model)
    spec_k: int = 3                    # max drafts per row; the verify step
                                       # always compiles at spec_k+1 positions
    spec_window: int = 8               # k-controller acceptance window

    def __post_init__(self):
        if self.backend != "paged":
            raise ValueError(f"backend={self.backend!r}: the paged engine "
                             "is the only serving engine")


class RequestHandle:
    """A live view of one submitted request.

    Tokens surface here as the engine samples them each ``step()``:
    ``new_tokens()`` drains whatever arrived since the last call (for
    open-loop callers driving ``Engine.step()`` themselves); iterating the
    handle drives the engine until this request finishes (other requests in
    the batch make progress on the same steps -- that *is* continuous
    batching).
    """

    def __init__(self, engine: "Engine", req: Request):
        self._engine = engine
        self._req = req
        self._cursor = 0

    # ------------- state -------------

    @property
    def rid(self) -> int:
        return self._req.rid

    @property
    def request(self) -> Request:
        return self._req

    @property
    def status(self) -> str:
        """queued | running | done | aborted | truncated | failed |
        rejected.  ``failed``: the engine quarantined the request after an
        unrecoverable fault (``request.detail`` says why); ``rejected``:
        admission control shed it before it decoded."""
        return self._req.status

    @property
    def finished(self) -> bool:
        return self._req.status in TERMINAL_STATUSES

    @property
    def output(self) -> List[int]:
        """All tokens sampled so far (does not move the stream cursor)."""
        return list(self._req.output)

    # ------------- streaming -------------

    def new_tokens(self) -> List[int]:
        """Tokens sampled since the last call (empty if none yet)."""
        out = self._req.output[self._cursor:]
        self._cursor += len(out)
        return out

    def __iter__(self) -> Iterator[int]:
        """Stream tokens, driving ``Engine.step()`` while none are pending.
        Terminates when this request reaches a terminal status (or the
        engine drains entirely, e.g. after an abort)."""
        while True:
            for tok in self.new_tokens():
                yield tok
            if self.finished:
                break
            if not self._engine.step():
                break                   # engine idle: nothing more can come
        for tok in self.new_tokens():   # tokens from the terminal step
            yield tok

    def result(self) -> Request:
        """Drive the engine until this request is terminal; returns it."""
        while not self.finished and self._engine.step():
            pass
        return self._req

    # ------------- control -------------

    def abort(self) -> bool:
        """Cancel now: frees pages immediately (spilled state too);
        tokens already streamed stay available.  Status -> ``aborted``."""
        return self._engine.abort(self)


class Engine:
    """The serving facade over the paged engine."""

    def __init__(self, params, cfg: ModelConfig,
                 scfg: ServeConfig = ServeConfig(), mesh_axes=None,
                 obs: Optional[Observability] = None):
        self.scfg = scfg
        self._eng = PagedServingEngine(params, cfg, scfg,
                                       mesh_axes=mesh_axes, obs=obs)
        self._rids = itertools.count()

    # ------------- properties -------------

    @property
    def engine(self):
        """The backing engine (escape hatch: pool, scheduler, bank_report)."""
        return self._eng

    @property
    def obs(self) -> Observability:
        """The observability bundle: metrics registry, trace buffer,
        lifecycle tracker, recompile watcher."""
        return self._eng.obs

    # ------------- observability -------------

    def save_trace(self, path: str) -> None:
        """Write the structured trace: Chrome-trace JSON (Perfetto) or
        JSONL for ``*.jsonl`` paths."""
        self.obs.save_trace(path)

    def prometheus_text(self) -> str:
        """The metrics registry in Prometheus text exposition format."""
        return self.obs.prometheus_text()

    def lifecycle(self, handle) -> Optional["RequestRecord"]:
        """Per-request span record (queue delay, TTFT, preemption cost)."""
        rid = handle.rid if isinstance(handle, RequestHandle) else int(handle)
        return self.obs.lifecycle.record(rid)

    # ------------- request lifecycle -------------

    def submit(self, prompt, *, max_new_tokens: int = 16,
               eos_id: Optional[int] = None, priority: int = 0,
               deadline: Optional[float] = None,
               retain: bool = False) -> RequestHandle:
        """Queue a new request; returns its streaming handle.

        ``retain=True`` keeps the finished request's pages
        pinned so it can serve as a ``fork()`` parent; pair it with
        ``release()`` when the prefix is no longer needed.
        """
        req = Request(rid=next(self._rids),
                      prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      priority=priority, deadline=deadline, retain=retain)
        self._eng.submit(req)
        return RequestHandle(self, req)

    def fork(self, parent: RequestHandle, tokens: Sequence[int] = (), *,
             max_new_tokens: int = 16, eos_id: Optional[int] = None,
             priority: int = 0, deadline: Optional[float] = None,
             retain: bool = False) -> RequestHandle:
        """Continue a finished, retained parent without re-prefilling.

        The child shares the parent's full prefix pages copy-on-write and
        feeds only ``tokens`` (the next user turn; may be empty for a pure
        sampled continuation) after the parent's final sampled token.  Its
        context is exactly ``parent.prompt + parent.output + tokens``.
        """
        if not parent.finished or parent.status != "done":
            raise ValueError(f"fork parent {parent.rid} is not done "
                             f"(status={parent.status}); drive it with "
                             "result() first")
        req = Request(rid=next(self._rids),
                      prompt=np.asarray(list(tokens), np.int32),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      priority=priority, deadline=deadline, retain=retain,
                      parent_rid=parent.rid)
        self._eng.submit(req)
        return RequestHandle(self, req)

    def abort(self, handle) -> bool:
        rid = handle.rid if isinstance(handle, RequestHandle) else int(handle)
        return self._eng.abort(rid)

    def release(self, handle) -> None:
        """Free a retained parent's pages (shared pages stay alive until the
        last fork drops its reference)."""
        rid = handle.rid if isinstance(handle, RequestHandle) else int(handle)
        self._eng.release_retained(rid)

    # ------------- event loop -------------

    def step(self) -> bool:
        """One event-loop iteration (admit + one batched decode step).
        Returns True while any request is queued or running."""
        return self._eng.step()

    def has_work(self) -> bool:
        return self._eng.has_work()

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drain-to-empty wrapper around ``step()`` (see the engine's docs
        for the ``max_steps`` still-active surfacing contract)."""
        return self._eng.run(max_steps=max_steps)

    def stats(self) -> Dict[str, float]:
        return self._eng.stats()

    # ------------- sessions -------------

    def session(self) -> "Session":
        return Session(self)


class Session:
    """Multi-turn chat on copy-on-write prefix sharing.

    Each ``send()`` forks the previous turn instead of re-prefilling the
    conversation so far: turn N costs one tail-page copy + the new tokens,
    regardless of how long the history is.  The previous turn's pages are
    released as soon as the fork holds its own references.
    """

    def __init__(self, engine: Engine):
        self._engine = engine
        self._prev: Optional[RequestHandle] = None

    @property
    def turns(self) -> Optional[RequestHandle]:
        """Handle of the latest turn (None before the first send)."""
        return self._prev

    def send(self, tokens, *, max_new_tokens: int = 16,
             eos_id: Optional[int] = None) -> RequestHandle:
        """Feed the next user turn; returns the reply's streaming handle."""
        if self._prev is None:
            h = self._engine.submit(tokens, max_new_tokens=max_new_tokens,
                                    eos_id=eos_id, retain=True)
            self._prev = h
            return h
        prev = self._prev
        prev.result()                        # finish the previous turn
        if prev.status != "done":
            raise RuntimeError(f"previous turn ended {prev.status}; "
                               "session context is gone")
        h = self._engine.fork(prev, tokens, max_new_tokens=max_new_tokens,
                              eos_id=eos_id, retain=True)
        # the fork takes its page references at admission: drive until the
        # child is running, then the old turn's pages can drop
        while h.status == "queued" and self._engine.step():
            pass
        if h.status != "queued":
            self._engine.release(prev)
        self._prev = h
        return h

    def close(self) -> None:
        """Release the last retained turn's pages."""
        if self._prev is not None:
            if self._prev.status == "done":
                self._engine.release(self._prev)
            self._prev = None
