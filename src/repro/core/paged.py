"""Paged decode-cache *views*: block-table-native operand containers.

The paged serving pool (``repro/serving/memory``) stores every KV leaf as a
page pool ``(n_pages, ..., R, 128)`` -- a page's tokens on the last axis,
its ``R`` values a token before it -- and every recurrent-state leaf as a
slab pool ``(n_slabs, ...)``.  Until the block-table-native kernels landed,
the decode step gathered those pools into dense per-step cache trees and
scattered one token back -- tripling the decode path's own DRAM traffic.

The two containers here make the paged layout a first-class *kernel* layout
instead of a host-side compatibility shim:

``PagedKVCache``
    One attention layer's K/V page pools plus the step's block table.  The
    ``layout="paged"`` SPU ops (``repro/ops/paged_ops.py``) walk
    ``bt[B, npg]`` directly -- the Pallas kernels scalar-prefetch the page
    ids and stream each 128-token page out of the pool in place; the
    ``kv_append`` op writes the new token's K/V row into its page slot via
    ``input_output_aliases``.  No dense copy of the context ever exists.

``PagedState``
    One mixer's recurrent-state slab pool plus the step's slab ids.  The
    paged ``state_update`` op updates exactly the ``B`` owned slab rows in
    place (the slabs are per-request already, so this is the minimal
    traffic): the fused MX8 kernel addresses each row's slab in the pool
    by scalar prefetch.

Both carry a ``group`` index: scanned models stack their per-group leaves
``(G, ...)`` inside the pool content, and one container is shared by all
``G`` layers of a pattern position -- the decode loop re-binds ``group``
(and the step's base ``lengths``) per scan iteration via :func:`with_group`.

``PAGE_TOKENS`` is defined here (the serving layer re-exports it): 128
tokens per page *is* the MX tile, which is what lets the Pallas grid walk
the block table with one page per tile.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import formats as F

#: tokens per KV page == the MX tile / kernel alignment unit.  The paged
#: attention grid assigns exactly one page to each flash tile.
PAGE_TOKENS = 128


def pages_for(n_tokens: int) -> int:
    """Pages that hold (and stream for) an ``n_tokens`` context.

    The single definition shared by the serving allocator, the paged ops'
    traffic descriptors, and the engines' traffic meter -- these must agree
    bit-for-bit, so the ceil/min-1 semantics live in exactly one place.
    """
    return max(1, -(-int(n_tokens) // PAGE_TOKENS))


def _payload_dims(k) -> Tuple[int, ...]:
    """Pool shape of a (possibly quantized) pooled stream."""
    if isinstance(k, F.QuantizedTensor):
        return tuple(k.payload["mantissa"].shape)
    return tuple(k.shape)


def _row_width(k) -> int:
    """Values a token of a stored page stream holds: its ``R``."""
    return _payload_dims(k)[2]


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class PagedKVCache:
    """Block-table view of one attention layer's shared K/V page pools.

    ``k``/``v`` hold the *whole pool* in the normalized stored layout
    ``(n_pages, G, KVH*d, PAGE_TOKENS)``, tokens on the lanes (``G = 1``
    for unstacked layers; quantized streams keep one pool per payload
    field, and their ``shape`` is the logical ``(n_pages, G, PAGE_TOKENS,
    KVH, d)``).  ``heads`` is ``KVH``.  ``bt`` is the step's dense block
    table, ``lengths`` the valid context per row, and ``group`` selects
    which stacked layer this view addresses.
    """
    k: object
    v: Optional[object]
    bt: jnp.ndarray                  # (B, npg) int32 physical page ids
    lengths: jnp.ndarray             # (B,) int32 valid cached positions
    group: jnp.ndarray               # () int32 stacked-layer index
    fmt: str = "mx8"
    v_width: Optional[int] = None    # MLA only
    lead_shape: Tuple[int, ...] = ()  # original group-axis shape (commit)
    heads: int = 1                   # KV heads a stored row holds

    def tree_flatten_with_keys(self):
        GK = jax.tree_util.GetAttrKey
        return ([(GK("k"), self.k), (GK("v"), self.v), (GK("bt"), self.bt),
                 (GK("lengths"), self.lengths), (GK("group"), self.group)],
                (self.fmt, self.v_width, self.lead_shape, self.heads))

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, bt, lengths, group = children
        return cls(k, v, bt, lengths, group, *aux)

    # -- logical geometry (read off the physical pools) -----------------

    @property
    def batch(self) -> int:
        return int(self.bt.shape[0])

    @property
    def n_page_slots(self) -> int:
        """Block-table width: pages the attention grid walks per row."""
        return int(self.bt.shape[1])

    @property
    def max_len(self) -> int:
        return self.n_page_slots * PAGE_TOKENS

    @property
    def kv_heads(self) -> int:
        return self.heads

    @property
    def dk(self) -> int:
        return _row_width(self.k) // self.heads

    @property
    def dv(self) -> int:
        if self.v is None:
            assert self.v_width is not None
            return self.v_width
        return _row_width(self.v) // self.heads

    def with_step(self, group, lengths: jnp.ndarray) -> "PagedKVCache":
        """Re-bind the view to one scan iteration: stacked-layer index plus
        the step's base lengths (the previous group's append bumped ours)."""
        return dataclasses.replace(self, group=jnp.asarray(group, jnp.int32),
                                   lengths=lengths)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class PagedState:
    """Slab-pool view of one mixer's recurrent state (``(B,H,dv,dk)`` rows
    living at ``pool[slab_id, group]``).  An MX8 pool's logical shape is
    ``(n_slabs, G, H, dv, dk)``; its payload is stored as the state-update
    kernel reads it (:mod:`repro.kernels.mx_state_update`)."""
    pool: object                     # (n_slabs, G, H, dv, d) pool (QT or array)
    slabs: jnp.ndarray               # (B,) int32 slab ids
    group: jnp.ndarray               # () int32 stacked-layer index
    fmt: str = "mx8"
    lead_shape: Tuple[int, ...] = ()

    def tree_flatten_with_keys(self):
        GK = jax.tree_util.GetAttrKey
        return ([(GK("pool"), self.pool), (GK("slabs"), self.slabs),
                 (GK("group"), self.group)],
                (self.fmt, self.lead_shape))

    @classmethod
    def tree_unflatten(cls, aux, children):
        pool, slabs, group = children
        return cls(pool, slabs, group, *aux)

    @property
    def batch(self) -> int:
        return int(self.slabs.shape[0])

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        """Logical dense-state shape (B, H, dv, dk) of the viewed rows."""
        n_slabs, g, h, dv, dk = self.pool.shape
        return (self.batch, h, dv, dk)

    def with_step(self, group, lengths=None) -> "PagedState":
        return dataclasses.replace(self, group=jnp.asarray(group, jnp.int32))


def is_paged(x) -> bool:
    return isinstance(x, (PagedKVCache, PagedState))


def split_paged(cache):
    """Split one element's cache tree into (carried, scanned) halves.

    Paged containers address shared pools and must live in the decode scan's
    *carry* (every group iteration updates the same pool); plain array
    leaves (conv tails, sLSTM carries) stay in the stacked ``(G, B, ...)``
    layout and scan as xs/ys.  Exactly one half is non-None at every node.
    """
    if cache is None:
        return None, None
    if is_paged(cache):
        return cache, None
    if isinstance(cache, dict):
        parts = {k: split_paged(v) for k, v in cache.items()}
        return ({k: p[0] for k, p in parts.items()},
                {k: p[1] for k, p in parts.items()})
    if isinstance(cache, tuple):
        parts = tuple(split_paged(v) for v in cache)
        return tuple(p[0] for p in parts), tuple(p[1] for p in parts)
    return None, cache


def merge_paged(carried, scanned):
    """Inverse of :func:`split_paged` (structure-directed overlay)."""
    if carried is None:
        return scanned
    if scanned is None or is_paged(carried):
        return carried
    if isinstance(carried, dict):
        return {k: merge_paged(carried[k], scanned.get(k))
                for k in carried}
    if isinstance(carried, tuple):
        return tuple(merge_paged(c, s) for c, s in zip(carried, scanned))
    return carried


def with_group(cache, group, lengths=None):
    """Re-bind every paged container in a carried tree to one scan step."""
    if cache is None:
        return None
    if isinstance(cache, PagedKVCache):
        return cache.with_step(group, cache.lengths if lengths is None
                               else lengths)
    if isinstance(cache, PagedState):
        return cache.with_step(group)
    if isinstance(cache, dict):
        return {k: with_group(v, group, lengths) for k, v in cache.items()}
    if isinstance(cache, tuple):
        return tuple(with_group(v, group, lengths) for v in cache)
    return cache
