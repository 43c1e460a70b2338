"""Cache-tree paging adapter: maps the model's decode-cache pytree onto
page / slab pools and back.

The decode caches of a model are an arbitrary pytree of

  * ``KVCache`` nodes -- quantized (or plain) K/V streams with a **time
    axis** that grows with the context.  These are paged: the time axis is
    cut into 128-token, MX-tile-aligned pages and each page lives at a
    physical page id shared by every KV leaf (page id ``p`` indexes slice
    ``[p]`` of every KV pool array).
  * fixed-size recurrent-state leaves (``QuantizedTensor`` payloads or plain
    arrays: SSM states, conv tails, sLSTM carries).  These are slab
    allocated: one slab id per request indexes one row of every slab pool.

Axes are discovered **exactly**, not guessed: the layout is probed by
building abstract cache skeletons at (B=1,T=128), (B=2,T=128) and
(B=1,T=256) and diffing shapes -- the axis that moves with B is the batch
axis, the one that moves with T is the time axis.  Group-stacked leaves
((G, B, T, ...) from scan-over-layers) fall out of the same probe.

A page pool stores its tokens on the last axis: a page whose content is
``(*lead, 128, *rest)`` is kept as ``(*lead, prod(rest), 128)``
(:attr:`LeafSpec.stored_shape`).  The last two axes are then lane-dense
whatever the head width, the TPU keeps the pool in the row-major layout the
paged kernels read, and no decode step copies a pool into another layout.
The host-side page blobs (spill, prefix-store demotion) keep the logical
``(n, 128, *lead, *rest)`` order.  Likewise the payload of an MX8 recurrent
state (the ``"S"`` leaves) is stored as the state-update kernel reads it
(:func:`repro.kernels.mx_state_update.stored_shape`): rows are converted
where they enter or leave the pool through a dense tree (prefill insert,
the gather reference path); spill blobs, forks and snapshots copy stored
rows as they are.

All gather/scatter functions are pure jnp and run inside jit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.core import paged as PG
from repro.core.paged import PAGE_TOKENS  # noqa: F401  (canonical home moved)
from repro.kernels import mx_state_update as SU
from repro.ops.base import fmt_of_state


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """One pooled array leaf of the cache tree."""
    kind: str              # "page" | "slab"
    batch_axis: int        # in leaf coordinates (stacked layout)
    time_axis: int         # leaf coordinates; -1 for slabs
    shape: Tuple[int, ...]  # template leaf shape at B=1, T=PAGE_TOKENS
    dtype: Any
    #: payload field of an MX8 recurrent state, stored in the state-update
    #: kernel's layout; logical content ``(*lead, H, dv, c)``
    mx8_field: Optional[str] = None

    @property
    def content_shape(self) -> Tuple[int, ...]:
        """Leaf shape with the batch axis removed (one page / one slab)."""
        s = list(self.shape)
        s.pop(self.batch_axis)
        return tuple(s)

    @property
    def state_dims(self) -> Tuple[int, int, int]:
        """``(H, dv, dk)`` of an MX8 state leaf."""
        h, dv, c = self.content_shape[-3:]
        return h, dv, (c if self.mx8_field == "mantissa" else c * F.MX8_GROUP)

    @property
    def stored_shape(self) -> Tuple[int, ...]:
        """One page or slab as its pool stores it: a page's tokens last, an
        MX8 state in the state-update kernel's layout."""
        if self.kind == "slab":
            if self.mx8_field is None:
                return self.content_shape
            return (self.content_shape[:-3]
                    + SU.stored_shape(self.mx8_field, *self.state_dims))
        c, ct = self.content_shape, self.content_time_axis
        return c[:ct] + (int(np.prod(c[ct + 1:])), c[ct])

    @property
    def content_time_axis(self) -> int:
        """Time axis position inside ``content_shape`` (pages only)."""
        assert self.kind == "page"
        return self.time_axis - (1 if self.batch_axis < self.time_axis else 0)

    @property
    def content_nbytes(self) -> int:
        return int(np.prod(self.content_shape)) * jnp.dtype(self.dtype).itemsize


def _is_array(x) -> bool:
    return isinstance(x, (jnp.ndarray, np.ndarray, jax.ShapeDtypeStruct))


def _diff_axis(a, b) -> int:
    """The single axis where shapes differ, or -1 if identical."""
    assert len(a.shape) == len(b.shape), (a.shape, b.shape)
    axes = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
    assert len(axes) <= 1, (a.shape, b.shape)
    return axes[0] if axes else -1


class CachePaging:
    """Flattens a model's cache tree into LeafSpecs and moves data between
    pooled storage and dense per-step cache pytrees."""

    def __init__(self, template, t_b2, t_t2):
        """``template`` is a *real* cache tree at (B=1, T=PAGE_TOKENS);
        ``t_b2``/``t_t2`` are abstract skeletons at (B=2, T) and (B, 2T)."""
        self.template = template
        self.specs: List[LeafSpec] = []
        #: storage format and ``(H, dv, dk)`` of each recurrent-state
        #: (``"S"``) leaf
        self.state_leaves: List[Tuple[str, Tuple[int, ...]]] = []
        self._build_specs(template, t_b2, t_t2, in_kv=False)

    # ------------------------------------------------------------------
    # traversal -- the one canonical order every operation below follows
    # ------------------------------------------------------------------

    def _build_specs(self, t, b2, t2, in_kv: bool, mx8_state=False,
                     field=None):
        if t is None:
            return
        if isinstance(t, AC.KVCache):
            self._build_specs(t.k, b2.k, t2.k, in_kv=True)
            self._build_specs(t.v, b2.v, t2.v, in_kv=True)
            # lengths is reconstructed from the request lengths vector,
            # not pooled -- no spec.
            return
        if isinstance(t, F.QuantizedTensor):
            for f in sorted(t.payload):
                self._build_specs(t.payload[f], b2.payload[f], t2.payload[f],
                                  in_kv=in_kv, field=f if mx8_state else None)
            return
        if isinstance(t, dict):
            for k in sorted(t):
                if k == "S":
                    self.state_leaves.append((fmt_of_state(t[k]),
                                              tuple(t[k].shape[-3:])))
                # an MX8 recurrent state is stored for the state update
                mx8 = k == "S" and self.state_leaves[-1][0] == "mx8"
                self._build_specs(t[k], b2[k], t2[k], in_kv=in_kv,
                                  mx8_state=mx8)
            return
        if isinstance(t, (tuple, list)):
            for a, b, c in zip(t, b2, t2):
                self._build_specs(a, b, c, in_kv=in_kv)
            return
        assert _is_array(t), type(t)
        b_ax = _diff_axis(t, b2)
        t_ax = _diff_axis(t, t2)
        assert b_ax >= 0, f"cache leaf {t.shape} does not scale with batch"
        if in_kv:
            assert t_ax >= 0 and t_ax != b_ax, \
                f"KV leaf {t.shape} has no time axis"
            self.specs.append(LeafSpec("page", b_ax, t_ax,
                                       tuple(t.shape), t.dtype))
        else:
            assert t_ax == -1, f"state leaf {t.shape} scales with T"
            self.specs.append(LeafSpec("slab", b_ax, -1,
                                       tuple(t.shape), t.dtype, field))

    # ------------------------------------------------------------------
    # pools
    # ------------------------------------------------------------------

    def make_pools(self, n_pages: int, n_slabs: int) -> List[jnp.ndarray]:
        """One pool array per spec: (n_pages, *content) / (n_slabs, *content).

        Slab pools replicate the template's *initial* state content (e.g.
        sLSTM's ``m = -1e30`` carry), so a freshly pinned slab is a valid
        zero-context state even before prefill overwrites it.
        """
        pools = []
        it = iter(self._iter_template_leaves(self.template))
        for spec in self.specs:
            leaf = next(it)
            if spec.kind == "page":
                pools.append(jnp.zeros((n_pages,) + spec.stored_shape,
                                       spec.dtype))
            else:
                content = jnp.squeeze(jnp.asarray(leaf), axis=spec.batch_axis)
                pools.append(jnp.broadcast_to(
                    self._store(content, spec)[None],
                    (n_slabs,) + spec.stored_shape).astype(spec.dtype))
        return pools

    def _iter_template_leaves(self, t):
        """Array leaves in spec order (KVCache lengths skipped)."""
        if t is None:
            return
        if isinstance(t, AC.KVCache):
            yield from self._iter_template_leaves(t.k)
            yield from self._iter_template_leaves(t.v)
            return
        if isinstance(t, F.QuantizedTensor):
            for f in sorted(t.payload):
                yield from self._iter_template_leaves(t.payload[f])
            return
        if isinstance(t, dict):
            for k in sorted(t):
                yield from self._iter_template_leaves(t[k])
            return
        if isinstance(t, (tuple, list)):
            for a in t:
                yield from self._iter_template_leaves(a)
            return
        yield t

    @property
    def page_nbytes(self) -> int:
        """Device bytes one page occupies across every KV pool."""
        return sum(s.content_nbytes for s in self.specs if s.kind == "page")

    @property
    def slab_nbytes(self) -> int:
        return sum(s.content_nbytes for s in self.specs if s.kind == "slab")

    # ------------------------------------------------------------------
    # per-leaf moves (all jnp, jit-safe)
    # ------------------------------------------------------------------

    @staticmethod
    def _store(x, spec: LeafSpec):
        """Pages ``(..., *lead, 128, *rest)`` -> stored ``(..., *lead, R,
        128)``; slabs ``(..., *content)`` -> stored."""
        if spec.kind == "slab":
            return (x if spec.mx8_field is None
                    else SU.store(spec.mx8_field, x))
        n_rest = len(spec.content_shape) - spec.content_time_axis - 1
        flat = x.reshape(x.shape[:x.ndim - n_rest] + (-1,))
        return jnp.swapaxes(flat, -1, -2)

    @staticmethod
    def _unstore(stored, spec: LeafSpec):
        """Inverse of :meth:`_store`."""
        if spec.kind == "slab":
            return (stored if spec.mx8_field is None
                    else SU.unstore(spec.mx8_field, stored, *spec.state_dims))
        rest = spec.content_shape[spec.content_time_axis + 1:]
        x = jnp.swapaxes(stored, -1, -2)
        return x.reshape(x.shape[:-1] + rest)

    @classmethod
    def _gather_page_leaf(cls, pool, bt, spec: LeafSpec):
        """pool (P, *stored), bt (B, npg) -> dense leaf (.., B, T, ..)."""
        ct = spec.content_time_axis
        g = cls._unstore(pool[bt], spec)               # (B, npg, *content)
        g = jnp.moveaxis(g, 1, 1 + ct)                 # (B, c.., npg, 128, ..)
        shape = (g.shape[:1 + ct]
                 + (g.shape[1 + ct] * g.shape[2 + ct],)
                 + g.shape[3 + ct:])
        g = g.reshape(shape)
        return jnp.moveaxis(g, 0, spec.batch_axis)

    @classmethod
    def _gather_slab_leaf(cls, pool, slabs, spec: LeafSpec):
        return jnp.moveaxis(cls._unstore(pool[slabs], spec), 0,
                            spec.batch_axis)

    @staticmethod
    def _scatter_token_leaf(pool, dense, bt, pos, spec: LeafSpec):
        """Write back the single token row each request appended at ``pos``."""
        ct = spec.content_time_axis
        B = pos.shape[0]
        phys = bt[jnp.arange(B), pos // PAGE_TOKENS]
        off = pos % PAGE_TOKENS
        d = jnp.moveaxis(dense, (spec.batch_axis, spec.time_axis), (0, 1))
        vals = d[jnp.arange(B), pos]                   # (B, *lead, *rest)
        vals = vals.reshape(vals.shape[:1 + ct] + (-1,))
        # pool (P, *lead, R, 128): the slot is a column of the page
        return pool.at[(phys,) + (slice(None),) * (ct + 1) + (off,)].set(vals)

    @classmethod
    def _scatter_slab_leaf(cls, pool, dense, slabs, spec: LeafSpec):
        vals = jnp.moveaxis(dense, spec.batch_axis, 0)
        return pool.at[slabs].set(cls._store(vals, spec))

    @staticmethod
    def _row_to_pages(row, spec: LeafSpec):
        """Row leaf (B=1 dense, T=npg*128) -> page stack (npg, 128, *rest)."""
        d = jnp.moveaxis(row, (spec.batch_axis, spec.time_axis), (0, 1))[0]
        npg = d.shape[0] // PAGE_TOKENS
        return d.reshape((npg, PAGE_TOKENS) + d.shape[1:])

    @classmethod
    def _insert_pages_leaf(cls, pool, pages_vals, page_ids, spec: LeafSpec):
        """Pages (npg, 128, *lead, *rest) into their stored slots."""
        ct = spec.content_time_axis
        vals = cls._store(jnp.moveaxis(pages_vals, 1, 1 + ct), spec)
        return pool.at[page_ids].set(vals.astype(pool.dtype))

    @classmethod
    def _extract_pages_leaf(cls, pool, page_ids, spec: LeafSpec):
        ct = spec.content_time_axis
        pages = cls._unstore(pool[page_ids], spec)     # (npg, *content)
        return jnp.moveaxis(pages, 1 + ct, 1)          # (npg, 128, ...)

    # ------------------------------------------------------------------
    # tree-level operations
    # ------------------------------------------------------------------

    def gather(self, pools: Sequence[jnp.ndarray], bt: jnp.ndarray,
               slabs: jnp.ndarray, lengths: jnp.ndarray):
        """Materialize the dense cache pytree for one decode step.

        bt (B, npg) physical page ids; slabs (B,); lengths (B,).
        Returns a cache tree structurally identical to the model's, with
        QuantizedTensor aux shapes patched to the gathered (B, T) so the
        MX kernels see the right logical geometry.
        """
        B = int(bt.shape[0])
        T = int(bt.shape[1]) * PAGE_TOKENS
        dense = []
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "page":
                dense.append(self._gather_page_leaf(pool, bt, spec))
            else:
                dense.append(self._gather_slab_leaf(pool, slabs, spec))
        it = iter(dense)
        return self._rebuild(self.template, it, B, T, lengths)

    def _rebuild(self, t, it, B, T, lengths, in_kv=False, kv_time_axis=1):
        if t is None:
            return None
        if isinstance(t, AC.KVCache):
            k = self._rebuild(t.k, it, B, T, lengths, True, t.time_axis)
            v = self._rebuild(t.v, it, B, T, lengths, True, t.time_axis)
            ln = jnp.broadcast_to(
                lengths.astype(t.lengths.dtype),
                t.lengths.shape[:-1] + (B,))
            return AC.KVCache(k, v, ln, t.fmt, t.v_width, t.time_axis)
        if isinstance(t, F.QuantizedTensor):
            payload = {f: next(it) for f in sorted(t.payload)}
            shape = list(t.shape)
            shape[0] = B
            if in_kv:
                shape[kv_time_axis] = T
            return F.QuantizedTensor(t.fmt, tuple(shape), payload)
        if isinstance(t, dict):
            return {k: self._rebuild(t[k], it, B, T, lengths, in_kv,
                                     kv_time_axis)
                    for k in sorted(t)}
        if isinstance(t, tuple):
            return tuple(self._rebuild(a, it, B, T, lengths, in_kv,
                                       kv_time_axis) for a in t)
        if isinstance(t, list):
            return [self._rebuild(a, it, B, T, lengths, in_kv, kv_time_axis)
                    for a in t]
        return next(it)

    def _iter_cache_leaves(self, t):
        """Array leaves of a *dense cache tree* in spec order."""
        yield from self._iter_template_leaves(t)

    def scatter_step(self, pools: Sequence[jnp.ndarray], new_caches,
                     bt: jnp.ndarray, slabs: jnp.ndarray,
                     lengths: jnp.ndarray) -> List[jnp.ndarray]:
        """Commit one decode step: the appended KV token row goes to its
        page, recurrent slabs are rewritten in place."""
        out = []
        it = self._iter_cache_leaves(new_caches)
        for pool, spec in zip(pools, self.specs):
            dense = next(it)
            if spec.kind == "page":
                out.append(self._scatter_token_leaf(pool, dense, bt,
                                                    lengths, spec))
            else:
                out.append(self._scatter_slab_leaf(pool, dense, slabs, spec))
        return out

    def insert_request(self, pools: Sequence[jnp.ndarray], row_caches,
                       page_ids: jnp.ndarray, slab: jnp.ndarray
                       ) -> List[jnp.ndarray]:
        """Pin a prefilled B=1 cache row into freshly allocated pages+slab."""
        out = []
        it = self._iter_cache_leaves(row_caches)
        for pool, spec in zip(pools, self.specs):
            row = next(it)
            if spec.kind == "page":
                vals = self._row_to_pages(row, spec)
                out.append(self._insert_pages_leaf(pool, vals, page_ids, spec))
            else:
                vals = jnp.moveaxis(row, spec.batch_axis, 0)[0]
                out.append(pool.at[slab].set(self._store(vals, spec)))
        return out

    def extract_request(self, pools: Sequence[jnp.ndarray],
                        page_ids: jnp.ndarray, slab: jnp.ndarray
                        ) -> List[jnp.ndarray]:
        """Pull one request's pages+slab out of the pools (for host spill);
        the slab rows as stored."""
        out = []
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "page":
                out.append(self._extract_pages_leaf(pool, page_ids, spec))
            else:
                out.append(pool[slab])
        return out

    def fork_copy(self, pools: Sequence[jnp.ndarray], src_page: jnp.ndarray,
                  dst_page: jnp.ndarray, src_slab: jnp.ndarray,
                  dst_slab: jnp.ndarray) -> List[jnp.ndarray]:
        """Copy-on-write fork: duplicate one physical page (the parent's
        partially filled tail -- the only page a forked child may later
        write inside) and the parent's slab row (recurrent state is mutated
        every step, so it is never shareable).  Full prefix pages are shared
        by reference, not touched here."""
        out = []
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "page":
                out.append(pool.at[dst_page].set(pool[src_page]))
            else:
                out.append(pool.at[dst_slab].set(pool[src_slab]))
        return out

    def copy_slab(self, pools: Sequence[jnp.ndarray], src_slab: jnp.ndarray,
                  dst_slab: jnp.ndarray) -> List[jnp.ndarray]:
        """Fork at an exact page boundary: only the slab row is copied (the
        child's first append opens a fresh page of its own)."""
        out = []
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "slab":
                out.append(pool.at[dst_slab].set(pool[src_slab]))
            else:
                out.append(pool)
        return out

    def insert_blob(self, pools: Sequence[jnp.ndarray], blob,
                    page_ids: jnp.ndarray, slab: jnp.ndarray
                    ) -> List[jnp.ndarray]:
        """Re-pin a spilled request (inverse of extract_request); the new
        physical page ids may differ from the ones it was evicted from."""
        out = []
        for pool, spec, vals in zip(pools, self.specs, blob):
            if spec.kind == "page":
                out.append(self._insert_pages_leaf(pool, jnp.asarray(vals),
                                                   page_ids, spec))
            else:
                out.append(pool.at[slab].set(jnp.asarray(vals)))
        return out

    def extract_pages(self, pools: Sequence[jnp.ndarray],
                      page_ids: jnp.ndarray) -> List[jnp.ndarray]:
        """Pull bare pages out of the page pools (no slab row) -- the unit of
        host-tier demotion for prefix-store nodes.  Returns one
        (npg, 128, *rest) array per *page* spec, in spec order."""
        out = []
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "page":
                out.append(self._extract_pages_leaf(pool, page_ids, spec))
        return out

    def insert_pages(self, pools: Sequence[jnp.ndarray], blob,
                     page_ids: jnp.ndarray) -> List[jnp.ndarray]:
        """Re-pin bare pages (inverse of :meth:`extract_pages`); slab pools
        pass through untouched."""
        out = []
        it = iter(blob)
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "page":
                out.append(self._insert_pages_leaf(pool, jnp.asarray(next(it)),
                                                   page_ids, spec))
            else:
                out.append(pool)
        return out

    def extract_slab(self, pools: Sequence[jnp.ndarray],
                     slab: jnp.ndarray) -> List[jnp.ndarray]:
        """Pull one slab row per *slab* spec (a recurrent-state snapshot)."""
        out = []
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "slab":
                out.append(pool[slab])
        return out

    def insert_slab(self, pools: Sequence[jnp.ndarray], blob,
                    slab: jnp.ndarray) -> List[jnp.ndarray]:
        """Write a snapshot back into one slab row (inverse of
        :meth:`extract_slab`); page pools pass through untouched."""
        out = []
        it = iter(blob)
        for pool, spec in zip(pools, self.specs):
            if spec.kind == "slab":
                out.append(pool.at[slab].set(jnp.asarray(next(it))))
            else:
                out.append(pool)
        return out

    # ------------------------------------------------------------------
    # block-table-native views (the steady-state decode path)
    # ------------------------------------------------------------------
    #
    # paged_view / commit replace gather / scatter_step in the decode loop:
    # KV pools become PagedKVCache views (zero-copy -- the group-axis
    # normalization is a reshape, and the pools are stored as the kernels
    # read them) that the layout="paged" SPU ops walk via
    # the block table; recurrent "S" leaves become PagedState slab views the
    # paged state_update op updates in place; only the small residual slab
    # leaves (conv tails, sLSTM carries) are gathered/scattered as B rows --
    # which is the minimal traffic, since every step rewrites them anyway.

    @staticmethod
    def _norm_groups(pool: jnp.ndarray, n_lead: int):
        """(n, *lead, *rest) -> ((n, G, *rest), lead): fold the group-stack
        axes into one.  A reshape, never a copy."""
        lead = pool.shape[1:1 + n_lead]
        g = 1
        for d in lead:
            g *= d
        return pool.reshape((pool.shape[0], g) + pool.shape[1 + n_lead:]), lead

    def _view_stream(self, t, take):
        """Template KV/state stream -> (pool-backed stream, lead shape,
        logical rest shape).  A page stream's logical shape is (P, G, 128,
        *rest); a slab stream's is (S, G, *content) with the state's
        logical content."""
        if t is None:
            return None, (), ()
        quantized = isinstance(t, F.QuantizedTensor)
        payload, specs = {}, {}
        for f in (sorted(t.payload) if quantized else [None]):
            pool, specs[f] = take()
            n_lead = (specs[f].content_time_axis if specs[f].kind == "page"
                      else len(specs[f].content_shape) - 3)
            payload[f], lead = self._norm_groups(pool, n_lead)
        main = "mantissa" if quantized else None
        spec, arr = specs[main], payload[main]
        rest = (spec.content_shape[spec.content_time_axis + 1:]
                if spec.kind == "page" else ())
        if not quantized:
            return arr, lead, rest
        shape = arr.shape[:2] + ((PAGE_TOKENS,) + rest if spec.kind == "page"
                                 else spec.content_shape[-3:])
        return F.QuantizedTensor(t.fmt, shape, payload), lead, rest

    def paged_view(self, pools: Sequence[jnp.ndarray], bt: jnp.ndarray,
                   slabs: jnp.ndarray, lengths: jnp.ndarray):
        """Build the paged cache-view tree for one decode step (zero-copy
        for KV pages and recurrent states; B-row gathers for residual slab
        leaves).  Structure matches the model's cache tree."""
        it = iter(zip(pools, self.specs))
        take = lambda: next(it)
        group0 = jnp.int32(0)

        def walk(t):
            if t is None:
                return None
            if isinstance(t, AC.KVCache):
                k, lead, rest = self._view_stream(t.k, take)
                v, _, _ = self._view_stream(t.v, take)
                return PG.PagedKVCache(k, v, bt, lengths, group0,
                                       t.fmt, t.v_width, tuple(lead),
                                       rest[0] if len(rest) > 1 else 1)
            if isinstance(t, dict):
                out = {}
                for key in sorted(t):
                    if key == "S":
                        s, lead, _ = self._view_stream(t[key], take)
                        fmt = (t[key].fmt
                               if isinstance(t[key], F.QuantizedTensor)
                               else fmt_of_state(t[key]))
                        out[key] = PG.PagedState(s, slabs, group0, fmt,
                                                 tuple(lead))
                    else:
                        out[key] = walk(t[key])
                return out
            if isinstance(t, (tuple, list)):
                return tuple(walk(a) for a in t)
            # residual slab leaf: must be a plain array -- a quantized leaf
            # outside a KVCache / "S" slot would expand to several specs and
            # silently misalign the pool iterator, so fail loudly instead
            assert _is_array(t), \
                f"paged_view: unsupported residual cache leaf {type(t)}"
            pool, spec = take()
            return self._gather_slab_leaf(pool, slabs, spec)

        return walk(self.template)

    def _commit_stream(self, stream, take):
        """Updated pool-backed stream -> pool arrays in spec order."""
        out = []
        if stream is None:
            return out
        arrays = ([stream.payload[f] for f in sorted(stream.payload)]
                  if isinstance(stream, F.QuantizedTensor) else [stream])
        for arr in arrays:
            _, spec = take()
            out.append(arr.reshape((arr.shape[0],) + spec.stored_shape))
        return out

    def commit(self, pools: Sequence[jnp.ndarray], new_caches,
               slabs: jnp.ndarray) -> List[jnp.ndarray]:
        """Commit one paged decode step: unwrap the (already updated) KV and
        state pools from the view containers and scatter the residual slab
        rows back.  The inverse traversal of :meth:`paged_view`."""
        it = iter(zip(pools, self.specs))
        take = lambda: next(it)
        out: List[jnp.ndarray] = []

        def walk(t, c):
            if t is None:
                return
            if isinstance(t, AC.KVCache):
                out.extend(self._commit_stream(c.k, take))
                out.extend(self._commit_stream(c.v, take))
                return
            if isinstance(t, dict):
                for key in sorted(t):
                    if key == "S":
                        out.extend(self._commit_stream(c[key].pool, take))
                    else:
                        walk(t[key], c[key])
                return
            if isinstance(t, (tuple, list)):
                for a, b in zip(t, c):
                    walk(a, b)
                return
            pool, spec = take()
            out.append(self._scatter_slab_leaf(pool, c, slabs, spec))

        walk(self.template, new_caches)
        return out

    def commit_select(self, pools: Sequence[jnp.ndarray], snaps,
                      slabs: jnp.ndarray, sel: jnp.ndarray
                      ) -> List[jnp.ndarray]:
        """Roll every slab row back to one selected speculative position.

        ``snaps`` is the snapshot tree a ``paged_spec_decode_step`` returns:
        it mirrors the cache tree, with every recurrent-state leaf stacked
        position-major to ``(n, B, *row)`` (``None`` under attention
        elements -- KV rollback is a host-side length reset, so page pools
        pass through untouched).  ``sel`` (B,) picks, per request, the last
        accepted position; row b of every slab pool is rewritten with
        ``snap[sel[b], b]``.  Requests that accepted every position rewrite
        their final state verbatim, so running this after :meth:`commit`
        is idempotent for them.
        """
        it = iter(zip(pools, self.specs))
        take = lambda: next(it)
        B = int(slabs.shape[0])
        bidx = jnp.arange(B)
        out: List[jnp.ndarray] = []

        def skip(t):
            for _ in self._iter_template_leaves(t):
                pool, _ = take()
                out.append(pool)

        def put(snap_leaf):
            pool, spec = take()
            assert spec.kind == "slab", \
                "snapshot leaf aligned with a page spec"
            vals = snap_leaf[sel, bidx]            # (B, *row)
            out.append(pool.at[slabs].set(
                vals.reshape((B,) + spec.stored_shape)))

        def walk(t, s):
            if t is None:
                return
            if s is None or isinstance(t, AC.KVCache):
                skip(t)
                return
            if isinstance(t, F.QuantizedTensor):
                for f in sorted(t.payload):
                    put(s[f])
                return
            if isinstance(t, dict):
                for key in sorted(t):
                    walk(t[key], s[key])
                return
            if isinstance(t, (tuple, list)):
                for a, b in zip(t, s):
                    walk(a, b)
                return
            put(s)

        walk(self.template, snaps)
        return out
