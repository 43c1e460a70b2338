"""Speculative-verify attention as a registered SpuOp (``spec_verify``).

One verify pass scores ``Kq`` query positions (the current token plus the
drafted ones) against a cache that already holds their appended K/V rows.
Query position ``j`` may attend to every cached position strictly before
its own row: with ``lengths`` counting the ``Kq`` freshly appended rows,

    position j sees  pos < lengths - (Kq - 1 - j)

so row ``j``'s output is bit-identical to the single-query ``attn_decode``
of the j-th *sequential* decode step (``Kq = 1`` degenerates exactly to
``attn_decode``).  This is the paper's bandwidth argument turned into an
op: the whole cache streams ONCE for all ``Kq`` positions -- the page reads
of one decode step amortized over the drafted tokens -- so ``traffic(plan)``
reports a single cache stream plus ``Kq``-scaled operand/output bytes, and
pimsim/roofline score the verify pass accordingly.

Backends mirror the decode-attention ops:

``pallas`` (mx8, dense + paged)
    :mod:`repro.kernels.mx_spec_attention`: the flash grid of the
    single-query kernel with the query block widened to ``Kq * G`` rows and
    a per-row causal mask; the paged variant walks the block table via
    scalar prefetch, pages streaming once for all queries.

``jnp`` (every format, dense + paged)
    Reference twin: one ``attention_decode_ref`` per query position with
    the shifted lengths, stacked.  The paged jnp op gathers the block table
    into the dense layout in-op (same ``_dense_view`` delegation as the
    paged ``attn_decode``) while still reporting page-granular traffic.

Entry points: :func:`spec_attend` (plan + dispatch one verify) and
:func:`attention_spec_step` (append the ``n`` new K/V rows with the exact
per-position seeds of ``n`` sequential ``kv_append`` calls, then verify).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.core.paged import PAGE_TOKENS, PagedKVCache, pages_for
from repro.kernels import ref as _ref
from repro.kernels.mx_spec_attention import (mx_paged_spec_attention_decode,
                                             mx_spec_attention_decode)
from repro.ops import registry
from repro.ops.attention import (_cache_dims, _cache_quant, _cache_row_vals,
                                 _layout_of, kv_append)
from repro.ops.base import (OPERAND_BYTES, OUTPUT_BYTES, OpPlan, SpuOp,
                            StateQuantConfig, TrafficBytes)
from repro.ops.platform import interpret_pallas


class _SpecVerifyBase(SpuOp):
    kind = "spec_verify"

    def traffic(self, plan: OpPlan) -> TrafficBytes:
        # the whole valid cache streams ONCE for all Kq positions (that is
        # the point of verification); only operands and outputs scale by Kq
        B, T, H, Kq = (plan.dim("B"), plan.dim("T"), plan.dim("H"),
                       plan.dim("Kq"))
        cache = B * T * _cache_row_vals(plan) * plan.bits_per_val / 8.0
        dv_out = plan.opt("v_width") or plan.dim("dv")
        return TrafficBytes(
            state_read=cache,
            operand_read=B * Kq * H * plan.dim("dk") * OPERAND_BYTES,
            output_write=B * Kq * H * dv_out * OUTPUT_BYTES)


class _SpecVerifyJnpMixin:
    """Reference semantics: per-position single-query attention, stacked."""

    def _dense_execute(self, cache: AC.KVCache, inputs: Dict[str, Any],
                       plan: OpPlan) -> jnp.ndarray:
        q = inputs["q"]                               # (B, Kq, H, dk)
        Kq = q.shape[1]
        scale, vw = plan.opt("scale"), plan.opt("v_width")
        if isinstance(cache.k, F.QuantizedTensor):
            if cache.fmt == "mx8" and cache.v is not None:
                outs = [_ref.mx_attention_decode_ref(
                            q[:, j], cache.k, cache.v,
                            cache.lengths - (Kq - 1 - j), scale)
                        for j in range(Kq)]
                return jnp.stack(outs, axis=1)
            kf = F.dequantize(cache.k)
            vf = kf[..., :vw] if cache.v is None else F.dequantize(cache.v)
        else:
            kf = cache.k.astype(jnp.float32)
            vf = (kf[..., :vw] if cache.v is None
                  else cache.v.astype(jnp.float32))
        outs = [_ref.attention_decode_ref(q[:, j], kf, vf,
                                          cache.lengths - (Kq - 1 - j), scale)
                for j in range(Kq)]
        return jnp.stack(outs, axis=1)


@registry.register
class SpecVerifyPallas(_SpecVerifyBase):
    """Fused dense spec-verify over the packed MX8 cache (GQA or MLA)."""
    backend = "pallas"
    formats = ("mx8",)

    def execute(self, cache: AC.KVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[AC.KVCache, jnp.ndarray]:
        out = mx_spec_attention_decode(
            inputs["q"], cache.k, cache.v, cache.lengths,
            scale=plan.opt("scale"), v_width=plan.opt("v_width"),
            t_block=plan.opt("t_block", 128), interpret=interpret_pallas())
        return cache, out


@registry.register
class SpecVerifyJnp(_SpecVerifyBase, _SpecVerifyJnpMixin):
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def execute(self, cache: AC.KVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[AC.KVCache, jnp.ndarray]:
        return cache, self._dense_execute(cache, inputs, plan)


class _PagedSpecVerifyBase(SpuOp):
    kind = "spec_verify"
    layout = "paged"

    def traffic(self, plan: OpPlan) -> TrafficBytes:
        # page-granular single stream: every touched page streams whole,
        # once, for all Kq queries -- which is what keeps the verify pass
        # within k x the attn_decode page reads (contract RC306)
        B, T, H, Kq = (plan.dim("B"), plan.dim("T"), plan.dim("H"),
                       plan.dim("Kq"))
        toks = pages_for(T) * PAGE_TOKENS
        cache = B * toks * _cache_row_vals(plan) * plan.bits_per_val / 8.0
        dv_out = plan.opt("v_width") or plan.dim("dv")
        bt_bytes = B * pages_for(T) * 4.0              # the block table walk
        return TrafficBytes(
            state_read=cache,
            operand_read=B * Kq * H * plan.dim("dk") * OPERAND_BYTES
            + bt_bytes,
            output_write=B * Kq * H * dv_out * OUTPUT_BYTES)


@registry.register
class PagedSpecVerifyPallas(_PagedSpecVerifyBase):
    """Fused paged verify: the block-table grid, query block widened by Kq."""
    backend = "pallas"
    formats = ("mx8",)

    def execute(self, cache: PagedKVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedKVCache, jnp.ndarray]:
        out = mx_paged_spec_attention_decode(
            inputs["q"], cache.k, cache.v, cache.bt, cache.group,
            cache.lengths, scale=plan.opt("scale"),
            v_width=plan.opt("v_width"), interpret=interpret_pallas())
        return cache, out


@registry.register
class PagedSpecVerifyJnp(_PagedSpecVerifyBase, _SpecVerifyJnpMixin):
    """Reference paged verify: gather-in-op + the dense jnp reference."""
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def execute(self, cache: PagedKVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[PagedKVCache, jnp.ndarray]:
        from repro.ops.paged_ops import _dense_view
        return cache, self._dense_execute(_dense_view(cache), inputs, plan)


# ---------------------------------------------------------------------------
# call-site entry points
# ---------------------------------------------------------------------------

def spec_attend(cache, q: jnp.ndarray, cfg: StateQuantConfig,
                scale: Optional[float] = None,
                t_block: int = 128) -> jnp.ndarray:
    """Verify-attention of q (B, Kq, H, dk) against a cache whose lengths
    already count the Kq appended rows; returns (B, Kq, H, dv) f32."""
    quant = _cache_quant(cache, cfg)
    dims = _cache_dims(cache)
    dims["H"] = q.shape[2]
    dims["Kq"] = q.shape[1]
    p = registry.plan("spec_verify", dims, quant, cfg.backend,
                      layout=_layout_of(cache),
                      scale=scale, v_width=cache.v_width, t_block=t_block)
    _, out = registry.execute(cache, {"q": q}, p)
    return out


def attention_spec_step(cache, k_new: jnp.ndarray,
                        v_new: Optional[jnp.ndarray], q: jnp.ndarray,
                        cfg: StateQuantConfig, *,
                        scale: Optional[float] = None, seed=0,
                        ):
    """One speculative step: append the n new K/V rows, then verify.

    k_new/v_new are (B, n, KVH, d), q is (B, n, H, dk).  Rows append one at
    a time with seed ``seed + i`` -- every element seed in the model is
    affine in the step seed with coefficient 1, so position i's append
    quantizes with exactly the bits the i-th sequential decode step would
    have used (the greedy-exactness guarantee rests on this).
    """
    n = k_new.shape[1]
    for i in range(n):
        cache = kv_append(cache, k_new[:, i:i + 1],
                          None if v_new is None else v_new[:, i:i + 1],
                          cfg, seed=seed + i)
    out = spec_attend(cache, q, cfg, scale=scale)
    return out, cache
