"""Block-table-native SPU ops: the ``layout="paged"`` registry entries.

These consume the paged containers of :mod:`repro.core.paged` directly --
the serving pool's page/slab pools plus the step's block table -- instead of
a gathered dense cache tree:

``attn_decode`` / ``mla_decode`` (pallas, mx8)
    :func:`repro.kernels.mx_paged_attention.mx_paged_attention_decode`: the
    flash grid walks ``bt[B, npg]`` via scalar prefetch, dequantizing one
    128-token page per tile straight from the shared pool.

``attn_decode`` / ``mla_decode`` (jnp, every format)
    Reference semantics for parity: gathers the block table's pages into the
    dense layout *inside the op* and runs the dense jnp reference, so paged
    logits are bit-identical to the dense-gather path by construction.  Its
    ``traffic(plan)`` still reports what a real paged read moves
    (page-granular streams), which is what the cost models consume.

``kv_append`` (pallas mx8 / jnp every format)
    Quantizes the new token's K/V rows with the *same* bits as the dense
    op (identical shapes and seed -> identical stochastic rounding) and
    writes them into their page slot (a column: the pools keep a page's
    tokens on the lanes) in place -- ``input_output_aliases`` on the pallas
    path, a one-slot ``.at[].set`` scatter on jnp.

``state_update`` (pallas mx8 / jnp every format)
    State slabs are per-request already, so the op touches exactly the
    ``B`` owned slab rows.  The pallas kernel
    (:func:`repro.kernels.mx_state_update.mx_paged_state_update`) takes the
    slab pool whole, aliased input to output, and blocks each row's heads
    straight out of ``pool[slabs[b], group]`` -- nothing is gathered or
    scattered around it.  The jnp reference gathers the rows, runs the
    registered *dense* op on them (bit-identical) and scatters them back.

Traffic descriptors are page-granular: attention reads whole 128-token
pages (``ceil(T/128)`` of them -- a partially-filled tail page still
streams), appends write one row, state updates touch one slab row --
no full-pool gather/scatter term exists for the steady-state decode loop.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.core.paged import (PAGE_TOKENS, PagedKVCache, PagedState,
                              pages_for)
from repro.kernels import mx_state_update as SU
from repro.kernels.mx_paged_attention import (mx_paged_attention_decode,
                                              mx_paged_kv_append)
from repro.ops import registry
from repro.ops.attention import _cache_row_vals
from repro.ops.base import (OPERAND_BYTES, OUTPUT_BYTES, OpPlan, SpuOp,
                            TrafficBytes)
from repro.ops.platform import interpret_pallas


def _gather_stream(pool, bt: jnp.ndarray, group, heads: int) -> Any:
    """Pool (P, G, KVH*w, 128) -> dense (B, npg*128, KVH, w) for one group."""
    def one(arr):
        g = arr[bt, jnp.asarray(group, jnp.int32)]     # (B, npg, R, 128)
        B, npg, R = g.shape[:3]
        g = jnp.swapaxes(g, 2, 3)                      # (B, npg, 128, R)
        return g.reshape(B, npg * PAGE_TOKENS, heads, R // heads)
    if isinstance(pool, F.QuantizedTensor):
        payload = {f: one(a) for f, a in pool.payload.items()}
        B, T = payload["mantissa"].shape[:2]
        shape = (B, T) + payload["mantissa"].shape[2:]
        return F.QuantizedTensor(pool.fmt, shape, payload)
    return one(pool)


def _dense_view(cache: PagedKVCache) -> AC.KVCache:
    """Materialize the block table's dense KVCache (jnp reference path)."""
    k = _gather_stream(cache.k, cache.bt, cache.group, cache.heads)
    v = (None if cache.v is None
         else _gather_stream(cache.v, cache.bt, cache.group, cache.heads))
    return AC.KVCache(k, v, cache.lengths, cache.fmt, cache.v_width)


# ---------------------------------------------------------------------------
# attn_decode / mla_decode
# ---------------------------------------------------------------------------

class _PagedAttnBase(SpuOp):
    layout = "paged"

    def traffic(self, plan: OpPlan) -> TrafficBytes:
        # page-granular: every touched page streams whole, once, read-only
        B, T, H = plan.dim("B"), plan.dim("T"), plan.dim("H")
        toks = pages_for(T) * PAGE_TOKENS
        cache = B * toks * _cache_row_vals(plan) * plan.bits_per_val / 8.0
        dv_out = plan.opt("v_width") or plan.dim("dv")
        bt_bytes = B * pages_for(T) * 4.0               # the block table walk
        return TrafficBytes(
            state_read=cache,
            operand_read=B * H * plan.dim("dk") * OPERAND_BYTES + bt_bytes,
            output_write=B * H * dv_out * OUTPUT_BYTES)


class _PagedAttnPallas(_PagedAttnBase):
    """Fused paged decode attention: the grid walks the block table."""
    backend = "pallas"
    formats = ("mx8",)

    def execute(self, cache: PagedKVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedKVCache, jnp.ndarray]:
        out = mx_paged_attention_decode(
            inputs["q"], cache.k, cache.v, cache.bt, cache.group,
            cache.lengths, scale=plan.opt("scale"),
            v_width=plan.opt("v_width"), interpret=interpret_pallas())
        return cache, out


class _PagedAttnJnp(_PagedAttnBase):
    """Reference paged attention: gather-in-op + the dense jnp reference."""
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def execute(self, cache: PagedKVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedKVCache, jnp.ndarray]:
        dense_op = registry.get_op(plan.kind, "jnp", plan.fmt, "dense")
        _, out = dense_op.execute(_dense_view(cache), inputs, plan)
        return cache, out


@registry.register
class PagedAttnDecodePallas(_PagedAttnPallas):
    kind = "attn_decode"


@registry.register
class PagedAttnDecodeJnp(_PagedAttnJnp):
    kind = "attn_decode"


@registry.register
class PagedMlaDecodePallas(_PagedAttnPallas):
    kind = "mla_decode"


@registry.register
class PagedMlaDecodeJnp(_PagedAttnJnp):
    kind = "mla_decode"


# ---------------------------------------------------------------------------
# kv_append
# ---------------------------------------------------------------------------

class _PagedKVAppendBase(SpuOp):
    kind = "kv_append"
    layout = "paged"

    def traffic(self, plan: OpPlan) -> TrafficBytes:
        # one page *slot* per row per new token -- never the whole cache
        B, n = plan.dim("B"), plan.dim("n")
        vals = B * n * _cache_row_vals(plan)
        bt_bytes = B * n * 4.0
        return TrafficBytes(state_write=vals * plan.bits_per_val / 8.0,
                            operand_read=vals * OPERAND_BYTES + bt_bytes)

    # -- shared: quantize the new rows with the dense op's exact bits ----

    def _quant_rows(self, cache: PagedKVCache, new: jnp.ndarray,
                    plan: OpPlan, seed) -> Tuple[jnp.ndarray, ...]:
        """(B, 1, KVH, d) -> payload rows ((B, KVH, w), ...) bit-identical
        to what the dense kv_append stores for the same (shape, seed)."""
        # the paged append writes exactly one page slot per row; multi-token
        # appends (chunked prefill) go through PagedStatePool.insert_prefill
        assert new.shape[1] == 1, \
            f"paged kv_append writes one token per step, got n={new.shape[1]}"
        if isinstance(cache.k, F.QuantizedTensor):
            bits = (F.sr_bits(new.shape, seed)
                    if plan.rounding == "stochastic" else None)
            q = F.quantize(new, cache.fmt, plan.rounding, bits)
            return tuple(q.payload[f][:, 0] for f in sorted(q.payload))
        return (new[:, 0],)

    def _pools_of(self, stream) -> Tuple[jnp.ndarray, ...]:
        if isinstance(stream, F.QuantizedTensor):
            return tuple(stream.payload[f] for f in sorted(stream.payload))
        return (stream,)

    def _rebuild(self, stream, pools: Tuple[jnp.ndarray, ...]):
        if isinstance(stream, F.QuantizedTensor):
            return F.QuantizedTensor(stream.fmt, stream.shape,
                                     dict(zip(sorted(stream.payload), pools)))
        return pools[0]


@registry.register
class PagedKVAppendJnp(_PagedKVAppendBase):
    """One-slot scatter into the page that owns position ``lengths``."""
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def _scatter(self, pools, rows, bt, group, lengths):
        B = bt.shape[0]
        phys = bt[jnp.arange(B), lengths // PAGE_TOKENS]
        off = lengths % PAGE_TOKENS
        grp = jnp.asarray(group, jnp.int32)
        return tuple(p.at[phys, grp, :, off].set(
                         r.reshape(B, -1).astype(p.dtype))
                     for p, r in zip(pools, rows))

    def execute(self, cache: PagedKVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedKVCache, None]:
        k_new, v_new = inputs["k"], inputs.get("v")
        seed = inputs.get("seed", 0)
        k_rows = self._quant_rows(cache, k_new, plan, seed)
        nk = self._rebuild(cache.k, self._scatter(
            self._pools_of(cache.k), k_rows, cache.bt, cache.group,
            cache.lengths))
        nv = cache.v
        if v_new is not None:
            v_rows = self._quant_rows(cache, v_new, plan, seed + 1)
            nv = self._rebuild(cache.v, self._scatter(
                self._pools_of(cache.v), v_rows, cache.bt, cache.group,
                cache.lengths))
        n = k_new.shape[1]
        return dataclasses.replace(cache, k=nk, v=nv,
                                   lengths=cache.lengths + n), None


@registry.register
class PagedKVAppendPallas(_PagedKVAppendBase):
    """In-place page-slot write via ``input_output_aliases``."""
    backend = "pallas"
    formats = ("mx8",)

    def execute(self, cache: PagedKVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedKVCache, None]:
        k_new, v_new = inputs["k"], inputs.get("v")
        seed = inputs.get("seed", 0)
        rows = list(self._quant_rows(cache, k_new, plan, seed))
        pools = list(self._pools_of(cache.k))
        nk_count = len(pools)
        if v_new is not None:
            rows += list(self._quant_rows(cache, v_new, plan, seed + 1))
            pools += list(self._pools_of(cache.v))
        out = mx_paged_kv_append(pools, rows, cache.bt, cache.group,
                                 cache.lengths, interpret=interpret_pallas())
        nk = self._rebuild(cache.k, out[:nk_count])
        nv = (cache.v if v_new is None
              else self._rebuild(cache.v, out[nk_count:]))
        n = k_new.shape[1]
        return dataclasses.replace(cache, k=nk, v=nv,
                                   lengths=cache.lengths + n), None


# ---------------------------------------------------------------------------
# state_update
# ---------------------------------------------------------------------------

class _PagedStateUpdateBase(SpuOp):
    kind = "state_update"
    layout = "paged"

    def in_place(self, heads: int, dv: int, dk: int) -> bool:
        """Whether the op updates a pool of ``(heads, dv, dk)`` states in
        place; otherwise it gathers the batch rows and scatters them back."""
        return False
    def traffic(self, plan: OpPlan) -> TrafficBytes:
        # identical bytes to the dense layout: the slabs are per-request, so
        # the op touches exactly the B owned rows (read + write in place)
        dense = registry.get_op("state_update", "jnp", plan.fmt, "dense")
        return dense.traffic(plan)


@registry.register
class PagedStateUpdatePallas(_PagedStateUpdateBase):
    """The fused MX8 kernel over the slab pool, in place."""
    backend = "pallas"
    formats = ("mx8",)

    def in_place(self, heads: int, dv: int, dk: int) -> bool:
        return SU.plan_blocks(heads, dv, dk) is not None

    def execute(self, state: PagedState, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedState, jnp.ndarray]:
        if not self.in_place(*state.shape[1:]):
            # no lane-aligned block of such heads: the reference
            return registry.get_op("state_update", "jnp", plan.fmt,
                                   "paged").execute(state, inputs, plan)
        pool, y = SU.mx_paged_state_update(
            state.pool, state.slabs, state.group, inputs["d"], inputs["k"],
            inputs["v"], inputs["q"],
            jnp.asarray(inputs.get("seed", 0), jnp.int32),
            rounding=plan.rounding, interpret=interpret_pallas())
        return dataclasses.replace(state, pool=pool), y


@registry.register
class PagedStateUpdateJnp(_PagedStateUpdateBase):
    """Reference: the rows gathered, through the dense op, scattered back."""
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def execute(self, state: PagedState, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedState, jnp.ndarray]:
        pool, slabs = state.pool, state.slabs
        grp = jnp.asarray(state.group, jnp.int32)
        dense_op = registry.get_op("state_update", "jnp", plan.fmt, "dense")
        if not isinstance(pool, F.QuantizedTensor):
            new_rows, y = dense_op.execute(pool[slabs, grp], inputs, plan)
            npool = pool.at[slabs, grp].set(new_rows.astype(pool.dtype))
            return dataclasses.replace(state, pool=npool), y
        # an MX8 pool keeps the kernel's stored layout
        mx8 = pool.fmt == "mx8"
        dims = state.shape[1:]
        rows = F.QuantizedTensor(pool.fmt, state.shape, {
            f: SU.unstore(f, a[slabs, grp], *dims) if mx8 else a[slabs, grp]
            for f, a in pool.payload.items()})
        new_rows, y = dense_op.execute(rows, inputs, plan)
        npool = F.QuantizedTensor(pool.fmt, pool.shape, {
            f: a.at[slabs, grp].set(SU.store(f, new_rows.payload[f]) if mx8
                                    else new_rows.payload[f])
            for f, a in pool.payload.items()})
        return dataclasses.replace(state, pool=npool), y
