"""Decode attention and KV-cache append as registered SpuOps.

Three op kinds live here:

``kv_append``   -- quantize the new token's K/V (or MLA latent) rows and
                   scatter them into the cache at each sequence's length.
``attn_decode`` -- one-token GQA attention of the current queries against
                   the packed cache.
``mla_decode``  -- the MLA variant: a single compressed latent stream whose
                   first ``v_width`` lanes double as values.

``append + attend`` used to be two ad-hoc functions on
``core/attention_cache``; they are now planned and dispatched through the
same registry as the state update, so the paged pool (which gathers pages
into a dense :class:`~repro.core.attention_cache.KVCache`) and the
contiguous fixed-slot pool share one entry point
(:func:`attention_decode_step`), and the cost models read the ops' own
``traffic(plan)`` descriptors.

Backends: ``pallas`` is the fused MX8 decode kernel (read-only GEMV streams,
paper §6.2); ``jnp`` covers every storage format with reference semantics.
``kv_append`` is jnp-only -- it is an XLA scatter, not an SPU compute op,
but it is registered so its write traffic is accounted the same way.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.kernels import ref as _ref
from repro.kernels.mx_attention import mx_attention_decode as _attn_pallas
from repro.ops import registry
from repro.ops.base import (OPERAND_BYTES, OUTPUT_BYTES, OpPlan, SpuOp,
                            StateQuantConfig, TrafficBytes, fmt_of_state)
from repro.ops.platform import interpret_pallas


def _cache_row_vals(plan: OpPlan) -> int:
    """Stored values per cached token across K and V streams."""
    return plan.dim("KVH") * (plan.dim("dk") + plan.dim("dv"))


# ---------------------------------------------------------------------------
# kv_append
# ---------------------------------------------------------------------------

@registry.register
class KVAppendJnp(SpuOp):
    """Quantize + scatter n new token rows into a KV cache."""
    kind = "kv_append"
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def execute(self, cache: AC.KVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[AC.KVCache, None]:
        k_new, v_new = inputs["k"], inputs.get("v")
        seed = inputs.get("seed", 0)
        if isinstance(cache.k, F.QuantizedTensor):
            bits = (F.sr_bits(k_new.shape, seed)
                    if plan.rounding == "stochastic" else None)
            qk = F.quantize(k_new, cache.fmt, plan.rounding, bits)
            payload = {f: AC._update_at(cache.k.payload[f], qk.payload[f],
                                        cache.lengths)
                       for f in cache.k.payload}
            nk = F.QuantizedTensor(cache.fmt, cache.k.shape, payload)
            nv = None
            if v_new is not None:
                bits_v = (F.sr_bits(v_new.shape, seed + 1)
                          if plan.rounding == "stochastic" else None)
                qv = F.quantize(v_new, cache.fmt, plan.rounding, bits_v)
                vpayload = {f: AC._update_at(cache.v.payload[f], qv.payload[f],
                                             cache.lengths)
                            for f in cache.v.payload}
                nv = F.QuantizedTensor(cache.fmt, cache.v.shape, vpayload)
        else:
            nk = AC._update_at(cache.k, k_new, cache.lengths)
            nv = (None if v_new is None
                  else AC._update_at(cache.v, v_new, cache.lengths))
        n = k_new.shape[1]
        return AC.KVCache(nk, nv, cache.lengths + n, cache.fmt, cache.v_width,
                          cache.time_axis), None

    def traffic(self, plan: OpPlan) -> TrafficBytes:
        B, n = plan.dim("B"), plan.dim("n")
        vals = B * n * _cache_row_vals(plan)
        return TrafficBytes(state_write=vals * plan.bits_per_val / 8.0,
                            operand_read=vals * OPERAND_BYTES)


# ---------------------------------------------------------------------------
# attn_decode / mla_decode
# ---------------------------------------------------------------------------

class _AttnDecodeBase(SpuOp):
    def traffic(self, plan: OpPlan) -> TrafficBytes:
        # score + attend stream the whole valid cache once, read-only
        B, T, H = plan.dim("B"), plan.dim("T"), plan.dim("H")
        cache = B * T * _cache_row_vals(plan) * plan.bits_per_val / 8.0
        dv_out = plan.opt("v_width") or plan.dim("dv")
        return TrafficBytes(
            state_read=cache,
            operand_read=B * H * plan.dim("dk") * OPERAND_BYTES,
            output_write=B * H * dv_out * OUTPUT_BYTES)


class _AttnDecodePallas(_AttnDecodeBase):
    """Fused decode attention over the packed MX8 cache (GQA or MLA)."""
    backend = "pallas"
    formats = ("mx8",)

    def execute(self, cache: AC.KVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[AC.KVCache, jnp.ndarray]:
        out = _attn_pallas(inputs["q"], cache.k, cache.v, cache.lengths,
                           scale=plan.opt("scale"),
                           v_width=plan.opt("v_width"),
                           t_block=plan.opt("t_block", 128),
                           interpret=interpret_pallas())
        return cache, out


class _AttnDecodeJnp(_AttnDecodeBase):
    """Reference decode attention for every storage format."""
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def execute(self, cache: AC.KVCache, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[AC.KVCache, jnp.ndarray]:
        q = inputs["q"]
        scale, vw = plan.opt("scale"), plan.opt("v_width")
        if isinstance(cache.k, F.QuantizedTensor):
            if cache.fmt == "mx8" and cache.v is not None:
                out = _ref.mx_attention_decode_ref(q, cache.k, cache.v,
                                                   cache.lengths, scale)
                return cache, out
            kf = F.dequantize(cache.k)
            vf = kf[..., :vw] if cache.v is None else F.dequantize(cache.v)
        else:
            kf = cache.k.astype(jnp.float32)
            vf = (kf[..., :vw] if cache.v is None
                  else cache.v.astype(jnp.float32))
        return cache, _ref.attention_decode_ref(q, kf, vf, cache.lengths, scale)


@registry.register
class AttnDecodePallas(_AttnDecodePallas):
    kind = "attn_decode"


@registry.register
class AttnDecodeJnp(_AttnDecodeJnp):
    kind = "attn_decode"


@registry.register
class MlaDecodePallas(_AttnDecodePallas):
    kind = "mla_decode"


@registry.register
class MlaDecodeJnp(_AttnDecodeJnp):
    kind = "mla_decode"


# ---------------------------------------------------------------------------
# call-site entry points
# ---------------------------------------------------------------------------

def attn_kind_of(cache) -> str:
    return "mla_decode" if cache.v_width is not None else "attn_decode"


def _layout_of(cache) -> str:
    """The container type selects the op layout: a PagedKVCache dispatches
    to the block-table-native ops, a dense KVCache to the dense ops."""
    from repro.core.paged import PagedKVCache
    return "paged" if isinstance(cache, PagedKVCache) else "dense"


def _cache_quant(cache, cfg: StateQuantConfig) -> StateQuantConfig:
    from repro.core.paged import PagedKVCache
    fmt = (cache.fmt if isinstance(cache, PagedKVCache)
           else fmt_of_state(cache.k))
    return StateQuantConfig(fmt=fmt, rounding=cfg.rounding,
                            backend=cfg.backend)


def _cache_dims(cache, n: int = 1) -> Dict[str, int]:
    from repro.core.paged import PagedKVCache
    if isinstance(cache, PagedKVCache):
        return dict(B=cache.batch, T=cache.max_len, KVH=cache.kv_heads,
                    dk=cache.dk, dv=0 if cache.v is None else cache.dv, n=n)
    B, T, KVH, dk = cache.k.shape
    dv = 0 if cache.v is None else cache.v.shape[-1]
    return dict(B=B, T=T, KVH=KVH, dk=dk, dv=dv, n=n)


def plan_attn_decode_dims(kind: str, dims: Dict[str, int],
                          cfg: StateQuantConfig, *, scale=None,
                          v_width=None, layout: str = "dense",
                          strict: bool = False) -> OpPlan:
    """Plan a decode-attention invocation from explicit dims (cost models)."""
    dims = dict(dims)
    dims.setdefault("H", dims["KVH"])
    return registry.plan(kind, dims, cfg, cfg.backend, layout=layout,
                         strict=strict, scale=scale, v_width=v_width)


def kv_append(cache, k_new: jnp.ndarray,
              v_new: Optional[jnp.ndarray], cfg: StateQuantConfig,
              seed=0):
    """Append one (or n) token(s): k_new (B, n, KVH, dk)."""
    quant = _cache_quant(cache, cfg)
    p = registry.plan("kv_append", _cache_dims(cache, n=k_new.shape[1]), quant,
                      cfg.backend, layout=_layout_of(cache))
    new_cache, _ = registry.execute(cache, {"k": k_new, "v": v_new,
                                            "seed": seed}, p)
    return new_cache


def attn_decode(cache, q: jnp.ndarray, cfg: StateQuantConfig,
                scale: Optional[float] = None,
                t_block: int = 128) -> jnp.ndarray:
    """Decode attention of current-token queries q (B,H,dk) vs the cache."""
    quant = _cache_quant(cache, cfg)
    dims = _cache_dims(cache)
    dims["H"] = q.shape[1]
    p = registry.plan(attn_kind_of(cache), dims, quant, cfg.backend,
                      layout=_layout_of(cache),
                      scale=scale, v_width=cache.v_width, t_block=t_block)
    _, out = registry.execute(cache, {"q": q}, p)
    return out


def attention_decode_step(cache, k_new: jnp.ndarray,
                          v_new: Optional[jnp.ndarray], q: jnp.ndarray,
                          cfg: StateQuantConfig, *,
                          scale: Optional[float] = None, seed=0,
                          ) -> Tuple[jnp.ndarray, AC.KVCache]:
    """One decode step: append the token's K/V, then attend.

    The single entry point for GQA and MLA; the cache container selects the
    layout (dense ``KVCache`` vs block-table ``PagedKVCache``).
    """
    cache = kv_append(cache, k_new, v_new, cfg, seed=seed)
    out = attn_decode(cache, q, cfg, scale=scale)
    return out, cache
