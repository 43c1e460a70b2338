"""Paged, bank-aware state/KV memory pool.

One ``PagedStatePool`` owns the physical decode-cache storage of a serving
engine:

  * **KV pages** -- every attention/MLA cache leaf is stored as
    ``(n_pages, ..., 128, ...)`` arrays; a physical page id addresses one
    128-token, MX-tile-aligned chunk across *all* KV leaves at once.
  * **state slabs** -- every fixed-size recurrent leaf (SSM state, conv
    tails, sLSTM carries) is ``(n_slabs, ...)``; one slab id per request.

A request owns a block table (list of page ids) plus one slab id.  Slot
reuse is copy-free: finishing or growing a request only moves integer ids
between free lists -- no cache-tree rewrite, which is what retires the old
``_recapacity`` per-prefill tree surgery from the serving hot path.

Placement is bank-aware (see :mod:`.placement`): page ids map to
(pseudo-channel, bank-pair) coordinates and allocation balances live load
across bank pairs, producing a real page map that
:func:`repro.core.pimsim.placement_step_latency` can score.

Preemption spills a victim's pages+slab to host memory bit-exactly; resume
re-pins them to fresh physical ids (identical logits, different placement).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops as OPS
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.core.paged import pages_for  # noqa: F401  (canonical home moved)
from repro.serving.memory.layout import PAGE_TOKENS, CachePaging
from repro.serving.memory.placement import BankAwarePlacement, BankTopology
from repro.serving.resilience import crc_blob, verify_blob


def bucket_pages(npg: int) -> int:
    """Round a page count up to a power of two to bound jit retraces."""
    return 1 << max(0, (npg - 1).bit_length())


@dataclasses.dataclass
class SpilledRequest:
    """Host-side copy of an evicted request's state (bit-exact).

    Copy-on-write aware: only *privately owned* pages are extracted into
    ``blob``.  Pages shared with other resident requests never leave the
    device -- the spilled request keeps its reference on them (recorded in
    ``shared`` as (block-table position, physical id)), so they cannot be
    freed or overwritten while it waits, and resume reuses the ids verbatim.
    A shared page therefore spills zero extra times.
    """
    blob: List[np.ndarray]
    n_pages: int                        # total block-table length
    length: int
    private_idx: List[int] = dataclasses.field(default_factory=list)
    shared: List[tuple] = dataclasses.field(default_factory=list)
    #: CRC32 of ``blob`` at extraction; resume/prefetch verify it before
    #: the bits re-enter the device (None = unchecked legacy blob)
    crc: Optional[int] = None

    @property
    def pages_needed(self) -> int:
        """Fresh pages a resume must allocate (private pages only)."""
        return len(self.private_idx)


class PagedStatePool:
    """Block/page-granular pool backing both KV caches and SSM states.

    Page id 0 and slab id 0 are reserved scratch targets for inactive decode
    rows; usable capacity is ``n_pages - 1`` pages / ``n_slabs - 1`` slabs.
    """

    def __init__(self, cfg: ModelConfig, n_pages: Optional[int] = None,
                 n_slabs: int = 9, byte_budget: Optional[int] = None,
                 topology: Optional[BankTopology] = None, mesh_axes=None,
                 decode_mode: str = "paged"):
        assert decode_mode in ("paged", "gather")
        self.cfg = cfg
        self.mesh_axes = mesh_axes
        self.decode_mode = decode_mode
        template = M.init_decode_caches(cfg, 1, PAGE_TOKENS)
        t_b2 = M.abstract_decode_caches(cfg, 2, PAGE_TOKENS)
        t_t2 = M.abstract_decode_caches(cfg, 1, 2 * PAGE_TOKENS)
        self.paging = CachePaging(template, t_b2, t_t2)
        # the recurrent-state leaves the paged decode step updates in the
        # pool in place, and those it gathers and scatters back
        in_place = [OPS.get_op("state_update", OPS.resolve_backend(
                        "state_update", fmt, cfg.state_quant.backend,
                        layout="paged"), fmt, "paged").in_place(*dims)
                    for fmt, dims in self.paging.state_leaves]
        self.state_slab_leaves = {"in_place": sum(in_place),
                                  "gathered": len(in_place) - sum(in_place)}

        if byte_budget is not None:
            assert n_pages is None, "give n_pages or byte_budget, not both"
            state_bytes = (n_slabs - 1) * self.paging.slab_nbytes
            per_page = max(self.paging.page_nbytes, 1)
            n_pages = 1 + max(1, (byte_budget - state_bytes) // per_page)
        assert n_pages is not None and n_pages >= 2 and n_slabs >= 2
        self.n_pages = int(n_pages)
        self.n_slabs = int(n_slabs)

        self.pools = self.paging.make_pools(self.n_pages, self.n_slabs)
        if topology is None:
            # size the coordinate space to the pool, so the conflict score
            # compares against a *reachable* ideal spread
            pch, pairs = 16, 8
            while pch * pairs > max(self.n_pages - 1, 1) and pch * pairs > 1:
                if pairs >= pch:
                    pairs = max(1, pairs // 2)
                else:
                    pch = max(1, pch // 2)
            topology = BankTopology(pch, pairs)
        self.placement = BankAwarePlacement(self.n_pages, topology)
        self._free_slabs: List[int] = list(range(1, self.n_slabs))
        self.page_table: Dict[int, List[int]] = {}     # rid -> page ids
        self.slab_of: Dict[int, int] = {}              # rid -> slab id

        # steady-state decode: block-table-native paged ops over donated
        # pools -- XLA updates page slots and slab rows in place instead of
        # copying every pool every token
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        # speculative verify: n positions per row in one pass, returning
        # per-position state snapshots; commit_spec rolls rejected drafts
        # back by rewriting slab rows from the selected snapshot
        self._decode_spec = jax.jit(self._decode_spec_impl,
                                    donate_argnums=(1,))
        self._commit_spec = jax.jit(self._commit_spec_impl,
                                    donate_argnums=(0,))
        # dense-gather reference path (parity tests; never donates, so
        # callers may hold pool snapshots around a reference step)
        self._decode_gather = jax.jit(self._decode_gather_impl)  # lint: disable=JH104
        self._insert = jax.jit(self.paging.insert_request,
                               donate_argnums=(0,))
        self._extract = jax.jit(self.paging.extract_request)
        self._insert_blob = jax.jit(self.paging.insert_blob,
                                    donate_argnums=(0,))
        self._fork_copy = jax.jit(self.paging.fork_copy, donate_argnums=(0,))
        self._copy_slab = jax.jit(self.paging.copy_slab, donate_argnums=(0,))

        # block-table-native op plans (layout="paged"): per-page stream
        # bytes and per-request slab bytes for the PIM bank model come from
        # the registered ops' own traffic descriptors, not local formulas
        entries = OPS.decode_op_plans(cfg, 1, PAGE_TOKENS, layout="paged")
        self._page_stream_bytes = sum(
            e.traffic.state_read for e in entries
            if e.kind in ("attn_decode", "mla_decode"))
        self._slab_rw_bytes = sum(
            e.traffic.state_total for e in entries
            if e.kind == "state_update")
        #: host-side ledger of bytes still moved by gather/scatter -- which
        #: after the block-table-native rewire is only preemption
        #: spill/resume, prefill insertion, and the one-page fork copy --
        #: never the decode loop
        self.gather_bytes = 0.0
        #: cumulative pages handed out by the allocator (register / grow /
        #: resume / the fork tail copy); copy-on-write shares are *not*
        #: counted here -- the gap versus an unshared run is the savings
        self.pages_allocated = 0
        #: cumulative extra references taken by fork() -- each one is a page
        #: a prefix-sharing-free pool would have had to allocate and fill
        self.shared_page_hits = 0
        #: optional repro.obs.Observability (see ``attach_obs``)
        self._obs = None
        #: optional repro.serving.faults.FaultPlan -- when installed (the
        #: engine wires ``ServeConfig.fault_plan`` / ``REPRO_FAULTS``
        #: through), allocation sites consult it for injected transient
        #: failures.  One ``is None`` test per site when disabled.
        self.faults = None

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def attach_obs(self, obs) -> None:
        """Attach an engine's :class:`repro.obs.Observability` bundle: the
        jitted pool steppers get recompile watchers, the placement mirrors
        page alloc/free/ref into the metrics registry, the gauges
        ``state_slab_leaves_in_place`` / ``_gathered`` count the
        recurrent-state leaves the decode step updates in the pool in place
        and those it gathers, and page movement
        (register / grow / fork / spill / resume / release) emits instants
        on the pool track."""
        self._obs = obs
        self._decode = obs.wrap_jit(self._decode, "pool.decode")
        self._decode_spec = obs.wrap_jit(self._decode_spec,
                                         "pool.decode_spec")
        self._commit_spec = obs.wrap_jit(self._commit_spec,
                                         "pool.commit_spec")
        self._decode_gather = obs.wrap_jit(self._decode_gather,
                                           "pool.decode_gather")
        self._insert = obs.wrap_jit(self._insert, "pool.prefill_insert")
        self._insert_blob = obs.wrap_jit(self._insert_blob,
                                         "pool.resume_insert")
        self.placement.metrics = obs.metrics
        for how, n in self.state_slab_leaves.items():
            obs.metrics.gauge(f"state_slab_leaves_{how}").set(n)

    def _span(self, name: str):
        """A span of the attached engine's trace (none on a bare pool)."""
        if self._obs is None:
            return contextlib.nullcontext()
        return self._obs.span(name)

    def _instant(self, name: str, **args) -> None:
        if self._obs is not None:
            self._obs.tracer.instant(name, cat="pool", track="pool", **args)

    def _inject(self, site: str, rid: Optional[int] = None,
                what: str = "") -> bool:
        """One fault-plan consult: True means the caller must fail now.
        Fires are mirrored into ``faults_injected_total{site=}`` and a
        ``cat="fault"`` trace instant."""
        if self.faults is None or not self.faults.should_fire(site, rid=rid):
            return False
        if self._obs is not None:
            self._obs.metrics.counter("faults_injected_total",
                                      site=site).inc()
            self._obs.tracer.instant(f"fault.{site}", cat="fault",
                                     track="pool", rid=rid, what=what)
        return True

    def _account_gather(self, nbytes: float) -> None:
        """Bytes moved by gather/scatter (spill/resume/prefill-insert/fork
        copies): the host ledger plus the metrics counter."""
        self.gather_bytes += nbytes
        if self._obs is not None:
            self._obs.metrics.counter("gather_bytes_total").inc(nbytes)

    # ------------------------------------------------------------------
    # allocation
    # ------------------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return self.placement.n_free

    @property
    def free_slabs(self) -> int:
        return len(self._free_slabs)

    @property
    def usable_pages(self) -> int:
        return self.placement.n_usable

    def can_admit(self, n_pages: int, n_slabs: int = 1) -> bool:
        return self.free_pages >= n_pages and self.free_slabs >= n_slabs

    def register(self, rid: int, n_pages: int) -> bool:
        """Claim a slab + ``n_pages`` pages for a new / resuming request."""
        assert rid not in self.page_table
        if not self.can_admit(n_pages):
            return False
        if self._inject("alloc", rid=rid, what="register"):
            return False                # injected transient shortage
        pages = self.placement.alloc(n_pages)
        if pages is None:
            return False
        self.page_table[rid] = pages
        self.slab_of[rid] = self._free_slabs.pop()
        self.pages_allocated += n_pages
        self._instant("pool.register", rid=rid, pages=n_pages)
        return True

    def grow(self, rid: int, n_new: int) -> bool:
        """Extend a request's block table -- copy-free, just new page ids."""
        if self._inject("alloc", rid=rid, what="grow"):
            return False                # injected transient shortage
        pages = self.placement.alloc(n_new)
        if pages is None:
            return False
        self.page_table[rid].extend(pages)
        self.pages_allocated += n_new
        self._instant("pool.grow", rid=rid, pages=n_new)
        return True

    def release(self, rid: int):
        """Drop a request's references: pages return to the free list only
        when the last owner drops them (copy-on-write forks keep shared
        prefix pages alive); the slab is always exclusive and frees now."""
        pages = self.page_table.pop(rid)
        self.placement.unref(pages)
        self._free_slabs.append(self.slab_of.pop(rid))
        self._instant("pool.release", rid=rid, pages=len(pages))

    def fork(self, parent_rid: int, child_rid: int, length: int) -> bool:
        """Copy-on-write fork: the child shares the parent's full (append-
        immutable) prefix pages by reference and gets a private copy of only
        the partially filled tail page plus the parent's slab row (recurrent
        state at ``length``).  Costs at most 1 page + 1 slab regardless of
        prefix length -- re-prefill is skipped entirely.

        ``length`` is the parent's cached context length.  The parent may
        keep running (or stay retained): its own tail stays private to it,
        and full pages are never written by either side (decode appends only
        at positions >= length).
        """
        assert child_rid not in self.page_table
        parent_pages = self.page_table[parent_rid]
        n_full, tail = divmod(length, PAGE_TOKENS)
        assert len(parent_pages) >= n_full + (1 if tail else 0), \
            (parent_rid, length, len(parent_pages))
        need = 1 if tail else 0
        if not self.can_admit(need):
            return False
        new_pages: List[int] = []
        if tail:
            got = self.placement.alloc(1)
            if got is None:
                return False
            new_pages = got
            self.pages_allocated += 1
        shared = list(parent_pages[:n_full])
        self.placement.ref(shared)
        self.shared_page_hits += len(shared)
        self.page_table[child_rid] = shared + new_pages
        slab = self._free_slabs.pop()
        self.slab_of[child_rid] = slab
        src_slab = jnp.int32(self.slab_of[parent_rid])
        if tail:
            self.pools = self._fork_copy(
                self.pools, jnp.int32(parent_pages[n_full]),
                jnp.int32(new_pages[0]), src_slab, jnp.int32(slab))
            self._account_gather(self.page_nbytes + self.slab_nbytes)
        else:
            self.pools = self._copy_slab(self.pools, src_slab,
                                         jnp.int32(slab))
            self._account_gather(self.slab_nbytes)
        self._instant("pool.fork", parent=parent_rid, child=child_rid,
                      shared_pages=len(shared), copied_pages=len(new_pages))
        if self._obs is not None:
            self._obs.metrics.counter("forks_total").inc()
            self._obs.metrics.counter(
                "shared_page_refs_total").inc(len(shared))
        return True

    # ------------------------------------------------------------------
    # data movement
    # ------------------------------------------------------------------

    def request_nbytes(self, n_pages: int) -> float:
        """Physical bytes one request's pages + slab occupy (spill size)."""
        return n_pages * self.page_nbytes + self.slab_nbytes

    def insert_prefill(self, rid: int, row_caches):
        """Pin a prefilled B=1 cache row (T must equal npg*PAGE_TOKENS)."""
        pages = jnp.asarray(self.page_table[rid], jnp.int32)
        slab = jnp.int32(self.slab_of[rid])
        self.pools = self._insert(self.pools, row_caches, pages, slab)
        self._account_gather(self.request_nbytes(len(self.page_table[rid])))

    def spill(self, rid: int, length: int) -> SpilledRequest:
        """Evict: copy the request's *private* pages + slab to host
        bit-exactly and free those device ids.  Pages shared with other
        requests (copy-on-write prefixes, refcount > 1) are not extracted:
        the spilled request keeps its reference, so the bits stay resident
        for the co-owners and the page cannot be reallocated underneath the
        waiting blob -- a shared page never spills twice."""
        pages = self.page_table[rid]
        private_idx = [i for i, p in enumerate(pages)
                       if self.placement.refcount(p) == 1]
        shared = [(i, p) for i, p in enumerate(pages)
                  if self.placement.refcount(p) > 1]
        priv = [pages[i] for i in private_idx]
        blob = self._extract(self.pools, jnp.asarray(priv, jnp.int32),
                             jnp.int32(self.slab_of[rid]))
        host = [np.asarray(x) for x in blob]
        # free only the private pages (refcount 1 -> 0) + the slab; shared
        # refs travel with the SpilledRequest
        self.page_table.pop(rid)
        self.placement.unref(priv)
        self._free_slabs.append(self.slab_of.pop(rid))
        self._account_gather(self.request_nbytes(len(priv)))
        self._instant("pool.spill", rid=rid, private_pages=len(priv),
                      shared_pages=len(shared))
        # checksum the host copy at the tier boundary: resume/prefetch
        # verify it, so a corrupted blob is detected instead of silently
        # poisoning decode
        return SpilledRequest(host, len(pages), length,
                              private_idx=private_idx, shared=shared,
                              crc=crc_blob(host))

    def resume(self, rid: int, sp: SpilledRequest) -> bool:
        """Re-pin a spilled request: private pages land on fresh physical
        ids, shared prefix pages are still resident and rejoin the block
        table verbatim (same bits, possibly a different bank placement for
        the private part)."""
        assert rid not in self.page_table
        if not self.can_admit(sp.pages_needed):
            return False
        # the blob is about to re-enter the device: a corrupted byte must
        # stop here (BlobCorruption), not surface as garbage logits
        verify_blob(sp.blob, sp.crc, "spill blob", rid=rid)
        if self._inject("alloc", rid=rid, what="resume"):
            return False                # injected transient shortage
        fresh = self.placement.alloc(sp.pages_needed)
        if fresh is None:
            return False
        self.pages_allocated += sp.pages_needed
        table = [0] * sp.n_pages
        for pos, pid in sp.shared:
            table[pos] = pid
        for pos, pid in zip(sp.private_idx, fresh):
            table[pos] = pid
        self.page_table[rid] = table
        slab = self._free_slabs.pop()
        self.slab_of[rid] = slab
        self.pools = self._insert_blob(self.pools, sp.blob,
                                       jnp.asarray(fresh, jnp.int32),
                                       jnp.int32(slab))
        self._account_gather(self.request_nbytes(sp.pages_needed))
        self._instant("pool.resume", rid=rid, pages=sp.pages_needed,
                      shared_pages=len(sp.shared))
        return True

    def drop_spilled(self, sp: SpilledRequest, rid: Optional[int] = None):
        """Abort a spilled request: release the references its blob holds on
        still-resident shared pages (the last owner to drop frees them).
        ``rid`` lets tiered subclasses release per-request host accounting."""
        self.placement.unref([pid for _, pid in sp.shared])
        sp.shared = []

    # ------------------------------------------------------------------
    # the decode step
    # ------------------------------------------------------------------

    def _decode_impl(self, params, pools, bt, slabs, lengths, tokens, seed):
        """Block-table-native step: the layout="paged" SPU ops read pages
        and slab rows straight from the (donated) pools -- no gathered
        dense cache tree exists in the steady-state loop."""
        views = self.paging.paged_view(pools, bt, slabs, lengths)
        logits, new_views = M.paged_decode_step(
            params, cfg=self.cfg, tokens=tokens, caches=views,
            lengths=lengths, seed=seed, mesh_axes=self.mesh_axes)
        pools = self.paging.commit(pools, new_views, slabs)
        return logits, pools

    def _decode_spec_impl(self, params, pools, bt, slabs, lengths, tokens,
                          seed):
        """Speculative verify step: tokens (B, n) run through the paged
        caches in one pass; the per-position state snapshots ride back so
        ``commit_spec`` can roll rejected positions back bit-exactly."""
        views = self.paging.paged_view(pools, bt, slabs, lengths)
        logits, new_views, snaps = M.paged_spec_decode_step(
            params, cfg=self.cfg, tokens=tokens, caches=views,
            lengths=lengths, seed=seed, mesh_axes=self.mesh_axes)
        pools = self.paging.commit(pools, new_views, slabs)
        return logits, pools, snaps

    def _commit_spec_impl(self, pools, snaps, slabs, sel):
        return self.paging.commit_select(pools, snaps, slabs, sel)

    def _decode_gather_impl(self, params, pools, bt, slabs, lengths, tokens,
                            seed):
        """Dense-gather reference step (the pre-paged-kernel data path):
        materialize the context, run the dense ops, scatter one token back.
        Kept for bit-exact parity testing against the paged ops."""
        caches = self.paging.gather(pools, bt, slabs, lengths)
        logits, new_caches = M.decode_step(
            params, cfg=self.cfg, tokens=tokens, caches=caches,
            lengths=lengths, seed=seed, mesh_axes=self.mesh_axes)
        pools = self.paging.scatter_step(pools, new_caches, bt, slabs, lengths)
        return logits, pools

    def block_table(self, rids: Sequence[Optional[int]],
                    min_pages: int = 1) -> np.ndarray:
        """Dense (B, npg_bucket) block table; absent rows use scratch ids.

        ``min_pages`` floors the (pre-bucketing) width: the speculative
        verify step appends n rows per request, so its table must span
        ``pages_for(length + n)`` even when a garbage-padded row does not
        own that many pages yet -- those appends land on the scratch page,
        like idle rows' writes, and are never read back.
        """
        npg = max([len(self.page_table[r]) for r in rids if r is not None],
                  default=1)
        npg = bucket_pages(max(npg, min_pages))
        # rows dim is the fixed decode-batch width and the page dim is
        # power-of-2 bucketed, so the trace set is bounded by design
        bt = np.zeros((len(rids), npg), np.int32)  # lint: disable=JH103
        shadow = getattr(self.placement, "_shadow", None)
        if shadow is not None:   # PL254: every addressed page must be live
            shadow.check_live(
                {pid for r in rids if r is not None
                 for pid in self.page_table[r]},
                what=f"block table for rids {[r for r in rids if r is not None]}")
        for i, r in enumerate(rids):
            if r is not None:
                pages = self.page_table[r]
                bt[i, :len(pages)] = pages
        return bt

    def decode(self, params, rids: Sequence[Optional[int]],
               tokens: np.ndarray, lengths: np.ndarray, seed: int):
        """Run one batched decode step over ``rids`` (None = idle row) and
        commit the pools.  Returns logits (B, V) on device.

        ``decode_mode="paged"`` (default) runs the block-table-native ops in
        place over the donated pools; ``"gather"`` runs the dense-gather
        reference path (parity testing; old pool buffers stay valid).
        The host part (``serve.prepare``) and the jitted call
        (``serve.dispatch``) are spans of the attached engine's trace.
        """
        with self._span("serve.prepare"):
            inputs = self._decode_inputs(rids, tokens, lengths, seed)
        step = self._decode if self.decode_mode == "paged" \
            else self._decode_gather
        with self._span("serve.dispatch"):
            logits, self.pools = step(params, self.pools, *inputs)
        return logits

    def _decode_inputs(self, rids, tokens, lengths, seed, min_pages=1):
        """Block table, slab ids, lengths, tokens and seed on the device."""
        return (jnp.asarray(self.block_table(rids, min_pages=min_pages)),
                self._slab_ids(rids), jnp.asarray(lengths, jnp.int32),
                jnp.asarray(tokens, jnp.int32), jnp.int32(seed))

    def _slab_ids(self, rids: Sequence[Optional[int]]):
        return jnp.asarray([self.slab_of.get(r, 0) if r is not None else 0
                            for r in rids], jnp.int32)

    def decode_spec(self, params, rids: Sequence[Optional[int]],
                    tokens: np.ndarray, lengths: np.ndarray, seed: int,
                    min_pages: int = 1):
        """Run one speculative verify step: tokens (B, n) per row, logits
        (B, n, V) back, plus the snapshot tree for ``commit_spec``.

        Position i of every row runs with the seeds of the sequential
        decode step ``seed + i``, so its logits row is bit-identical to
        decoding that token in a normal step.  ``min_pages`` must span
        ``pages_for(length + n)`` over the batch (see :meth:`block_table`).
        """
        assert self.decode_mode == "paged", \
            "speculative decode requires the block-table-native path"
        with self._span("serve.prepare"):
            inputs = self._decode_inputs(rids, tokens, lengths, seed,
                                         min_pages=min_pages)
        with self._span("serve.dispatch"):
            logits, self.pools, snaps = self._decode_spec(
                params, self.pools, *inputs)
        return logits, snaps

    def commit_spec(self, rids: Sequence[Optional[int]], snaps,
                    sel: np.ndarray) -> None:
        """Roll recurrent state back to each row's last accepted position
        (``sel`` (B,), an index into the verify step's n positions).  KV
        needs no rollback -- the engine's host lengths mask rejected rows
        and later appends overwrite them."""
        self.pools = self._commit_spec(self.pools, snaps,
                                       self._slab_ids(rids),
                                       jnp.asarray(sel, jnp.int32))

    # ------------------------------------------------------------------
    # accounting
    # ------------------------------------------------------------------

    @property
    def page_nbytes(self) -> int:
        return self.paging.page_nbytes

    @property
    def slab_nbytes(self) -> int:
        return self.paging.slab_nbytes

    def bytes_total(self) -> int:
        """Usable pool bytes (scratch page/slab excluded)."""
        return (self.usable_pages * self.page_nbytes
                + (self.n_slabs - 1) * self.slab_nbytes)

    def occupancy(self) -> float:
        """Fraction of usable pages currently pinned."""
        used = self.usable_pages - self.free_pages
        return used / max(self.usable_pages, 1)

    # ------------------------------------------------------------------
    # shadow-ledger sanitizer (REPRO_SANITIZE=1)
    # ------------------------------------------------------------------

    def sanitizer_owned_pages(self) -> set:
        """Every page some owner can still account for: resident request
        block tables here; tiered pools add staged prefetches and resident
        prefix-store nodes.  Spilled requests' shared pages are owned by
        the engine-held SpilledRequest, so teardown checks only run once
        the engine has fully drained."""
        return {pid for pages in self.page_table.values() for pid in pages}

    def sanitizer_check_leaks(self, what: str = "engine teardown") -> None:
        """``PL255``: raise if the shadow ledger sees live pages no owner
        accounts for.  No-op unless ``REPRO_SANITIZE=1`` attached a ledger."""
        shadow = getattr(self.placement, "_shadow", None)
        if shadow is not None:
            shadow.assert_no_leaks(self.sanitizer_owned_pages(), what=what)

    @property
    def shared_page_savings(self) -> int:
        """Physical pages currently saved by copy-on-write sharing: extra
        references beyond one owner per live page."""
        return self.placement.n_shared_extra

    @property
    def shared_savings_peak(self) -> int:
        """High-water mark of :attr:`shared_page_savings` -- survives
        request release, so end-of-run stats still show what sharing saved."""
        return self.placement.shared_extra_peak

    def fragmentation(self, lengths: Dict[int, int]) -> float:
        """1 - used_tokens / allocated_token_capacity over resident requests
        (internal fragmentation of the last partially-filled pages)."""
        alloc_tokens = sum(len(p) for p in self.page_table.values()) \
            * PAGE_TOKENS
        used_tokens = sum(lengths.get(r, 0) for r in self.page_table)
        if alloc_tokens == 0:
            return 0.0
        return 1.0 - used_tokens / alloc_tokens

    def bank_traffic(self, rids: Sequence[int]) -> np.ndarray:
        """Column bursts per (pseudo-channel, bank-pair) for one decode step
        over ``rids``: every resident page is streamed once (the paged
        attention ops read whole 128-token pages in place), every slab row
        is read+written by the paged state-update op.

        Bytes come from the ``layout="paged"`` ops' own ``traffic(plan)``
        descriptors (page-granular reads, one-slot writes) -- the same
        numbers the serving stats account -- so
        :func:`repro.core.pimsim.placement_step_latency` scores exactly the
        traffic the dispatched ops move.
        """
        burst = 32.0
        page_lists = [self.page_table[r] for r in rids if r in self.page_table]
        m = self.placement.traffic_map(page_lists,
                                       self._page_stream_bytes / burst)
        topo = self.placement.topo
        for r in rids:
            s = self.slab_of.get(r)
            if s is not None:
                m[topo.coord(s)] += self._slab_rw_bytes / burst
        return m
