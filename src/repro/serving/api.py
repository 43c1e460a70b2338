"""Request-lifecycle serving API: one streaming ``Engine`` facade.

The batch-offline engines (``submit()`` everything, ``run()`` to drain,
read ``done`` at the end) become a request-lifecycle API shaped like a real
serving front-end:

  * one ``ServeConfig`` subsumes ``EngineConfig`` / ``PagedEngineConfig``
    and selects the fixed-slot or paged backend;
  * ``Engine.submit()`` returns a ``RequestHandle`` that streams tokens as
    they are sampled each ``step()``, exposes the terminal status
    (``done`` / ``aborted`` / ``truncated``) and can ``abort()`` mid-decode
    (pages/slots free immediately, spilled victims included);
  * ``Engine.step()`` is the explicit event loop -- drive it open-loop,
    interleaving submits/aborts between steps; ``run()`` stays as the
    drain-to-empty wrapper;
  * ``Engine.fork()`` (paged backend) starts a continuation of a retained
    parent via copy-on-write prefix sharing: the child references the
    parent's full prefix pages and copies only the partial tail page, so N
    sampled continuations of one prompt or the next turn of a chat skip
    re-prefilling the shared context entirely.  ``Session`` wraps that into
    multi-turn chat;
  * ``ServeConfig(prefix_cache=True)`` (paged backend) makes that sharing
    *automatic and cross-request*: a radix prefix store remembers every
    full prompt page served, and any later ``submit()`` whose prompt shares
    the prefix adopts the stored pages -- no explicit ``fork()``.  Stored
    pages outlive their request under ``prefix_store_pages`` (LRU), can be
    demoted to a ``host_tier_bytes``-budgeted host tier, and come back via
    scheduler-lookahead async prefetch (see ``serving/memory/tiered``).

    eng = Engine(params, cfg, ServeConfig(backend="paged"))
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
from repro.serving.engine import (EngineConfig, PagedEngineConfig,
                                  PagedServingEngine, Request, ServingEngine,
                                  TERMINAL_STATUSES)
from repro.serving.sampler import SamplingConfig
from repro.serving.scheduler import SchedulerConfig

__all__ = ["ServeConfig", "Engine", "RequestHandle", "Session", "Request"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """One config for both serving backends.

    ``backend="slots"`` serves from the fixed ``batch x cache_capacity``
    cache pool; ``backend="paged"`` serves from the paged, bank-aware
    state/KV pool (preempting scheduler, chunked prefill, copy-on-write
    prefix sharing / sessions).
    """
    backend: str = "paged"             # "paged" | "slots"
    batch: int = 4                     # decode rows (slots / decode batch)
    cache_capacity: int = 256          # slots backend: max context per slot
    n_pages: Optional[int] = 33        # paged: pool pages (incl. 1 scratch)
    n_slabs: Optional[int] = None      # paged: state slabs (default 2B+1)
    byte_budget: Optional[int] = None  # paged: alternative to n_pages
    prefill_chunk: int = 128           # paged: longest full-seq prefill
    prefill_buckets: Optional[Tuple[int, ...]] = None
                                       # paged: prefill lengths (bounded
                                       # compile count): a covered prompt is
                                       # padded up to its bucket, the padding
                                       # masked; a longer one streams its
                                       # tail through decode
    sampling: SamplingConfig = SamplingConfig()
    scheduler: SchedulerConfig = SchedulerConfig()
    seed: int = 0
    # --- tiered memory hierarchy (paged backend only) ---
    prefix_cache: bool = False         # radix prefix store: requests that
                                       # share a prompt prefix with earlier
                                       # requests adopt its pages, no fork()
    prefix_store_pages: int = 64       # store capacity in pages (LRU)
    host_tier_bytes: Optional[int] = None  # host DRAM budget (None = off)
    prefetch_window: int = 2           # lookahead prefetch depth
    # --- resilience / fault injection (paged backend only) ---
    fault_plan: Optional[str] = None   # fault spec string (see
                                       # serving/faults); REPRO_FAULTS env
                                       # applies when unset
    nan_guard: Optional[bool] = None   # post-step non-finite-logits guard
                                       # (None = on iff faults active)
    max_queued: Optional[int] = None   # admission control: queue-depth cap,
                                       # excess submits end ``rejected``
    request_timeout_s: Optional[float] = None  # max queue wait -> rejected
    step_budget_s: Optional[float] = None      # watchdog wall-clock budget
    # --- speculative decoding (paged backend only) ---
    spec: Optional[str] = None         # draft source: "ngram" (self-draft)
                                       # or "model:<arch>" (small model)
    spec_k: int = 3                    # max drafts verified per step
    spec_window: int = 8               # k-controller acceptance window

    def __post_init__(self):
        if self.backend not in ("paged", "slots"):
            raise ValueError(f"backend must be 'paged' or 'slots', "
                             f"got {self.backend!r}")
        if self.backend == "slots" and self.prefix_cache:
            raise ValueError("prefix_cache needs the paged backend "
                             "(page refcounts / block tables)")
        if self.backend == "slots" and self.spec is not None:
            raise ValueError("speculative decoding needs the paged backend "
                             "(the spec_verify step walks block tables and "
                             "rolls state slabs back)")
        if self.backend == "slots":
            for f in ("fault_plan", "nan_guard", "max_queued",
                      "request_timeout_s", "step_budget_s"):
                if getattr(self, f) is not None:
                    raise ValueError(
                        f"{f} needs the paged backend (the resilience "
                        "layer lives in the paged engine/pool)")

    def engine_config(self):
        """The backend-specific config this ServeConfig lowers to."""
        if self.backend == "slots":
            return EngineConfig(slots=self.batch,
                                cache_capacity=self.cache_capacity,
                                sampling=self.sampling, seed=self.seed)
        return PagedEngineConfig(
            max_decode_batch=self.batch,
            n_pages=None if self.byte_budget is not None else self.n_pages,
            n_slabs=(self.n_slabs if self.n_slabs is not None
                     else 2 * self.batch + 1),
            byte_budget=self.byte_budget,
            prefill_chunk=self.prefill_chunk,
            prefill_buckets=self.prefill_buckets,
            sampling=self.sampling,
            scheduler=self.scheduler,
            seed=self.seed,
            prefix_cache=self.prefix_cache,
            prefix_store_pages=self.prefix_store_pages,
            host_tier_bytes=self.host_tier_bytes,
            prefetch_window=self.prefetch_window,
            fault_plan=self.fault_plan,
            nan_guard=self.nan_guard,
            max_queued=self.max_queued,
            request_timeout_s=self.request_timeout_s,
            step_budget_s=self.step_budget_s,
            spec=self.spec,
            spec_k=self.spec_k,
            spec_window=self.spec_window)


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
        """Cancel now: frees pages/slots immediately (spilled state too);
        tokens already streamed stay available.  Status -> ``aborted``."""
        return self._engine.abort(self)


class Engine:
    """The one serving facade over both backends."""

    def __init__(self, params, cfg: ModelConfig,
                 scfg: ServeConfig = ServeConfig(), mesh_axes=None,
                 obs: Optional[Observability] = None):
        self.scfg = scfg
        obs = obs if obs is not None else Observability()
        ecfg = scfg.engine_config()
        if scfg.backend == "slots":
            self._eng = ServingEngine(params, cfg, ecfg, mesh_axes=mesh_axes,
                                      obs=obs)
        else:
            self._eng = PagedServingEngine(params, cfg, ecfg,
                                           mesh_axes=mesh_axes, obs=obs)
        self._rids = itertools.count()

    # ------------- properties -------------

    @property
    def backend(self) -> str:
        return self._eng.backend

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

        ``retain=True`` (paged backend) keeps the finished request's pages
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
        Paged backend only.
        """
        if self.backend != "paged":
            raise ValueError("fork() needs the paged backend "
                             "(copy-on-write prefix sharing)")
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
        """Drain-to-empty wrapper around ``step()`` (see the engines' docs
        for the ``max_steps`` still-active surfacing contract)."""
        return self._eng.run(max_steps=max_steps)

    def stats(self) -> Dict[str, float]:
        return self._eng.stats()

    # ------------- sessions -------------

    def session(self) -> "Session":
        if self.backend != "paged":
            raise ValueError("sessions need the paged backend "
                             "(copy-on-write prefix sharing)")
        return Session(self)


class Session:
    """Multi-turn chat on copy-on-write prefix sharing.

    Each ``send()`` forks the previous turn instead of re-prefilling the
    conversation so far: turn N costs one tail-page copy + the new tokens,
    regardless of how long the history is.  The previous turn's pages are
    released as soon as the fork holds its own references.
    """

    def __init__(self, engine: Engine):
        assert engine.backend == "paged"
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
