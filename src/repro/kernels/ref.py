"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are tested against (shape/dtype sweeps
with assert_allclose) and double as the slow-but-obviously-correct fallback.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import formats as F


# ---------------------------------------------------------------------------
# Generalized state update (paper Eq. 2), float path
# ---------------------------------------------------------------------------

def state_update_ref(S: jnp.ndarray, d: jnp.ndarray, k: jnp.ndarray,
                     v: jnp.ndarray, q: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token step of  S' = d ⊙ S + k vᵀ ;  y = S'ᵀ q.

    Shapes (B = batch, H = heads):
      S: (B, H, dk, dv) f32      d: (B, H, dk) or (B, H, 1)
      k, q: (B, H, dk)           v: (B, H, dv)
    Returns (S', y) with y: (B, H, dv).
    """
    S = S.astype(jnp.float32)
    d_ = d.astype(jnp.float32)[..., None]                    # (B,H,dk,1)
    Sn = d_ * S + k.astype(jnp.float32)[..., None] * v.astype(jnp.float32)[..., None, :]
    y = jnp.einsum("bhkv,bhk->bhv", Sn, q.astype(jnp.float32))
    return Sn, y


def decay_add(S: jnp.ndarray, d: jnp.ndarray, v: jnp.ndarray,
              k: jnp.ndarray) -> jnp.ndarray:
    """``S * d + v * k`` with each product rounded on its own.

    A compiler may contract ``a * b + c`` into one FMA, which rounds once;
    XLA:CPU does so in some fused loops and not in others, so the same
    expression would round differently in the kernel and in this oracle.
    Adding a zero made at run time (``d * 0``) to each product leaves it
    unchanged and leaves the final add no product to contract.  The MX8
    kernel evaluates its update through this function too.
    """
    return (S * d + d * 0.0) + (v * k + k * 0.0)


# ---------------------------------------------------------------------------
# Quantized state update: dequant -> update -> requant(SR) -> output GEMV
# ---------------------------------------------------------------------------

def quantized_state_update_ref(
    qS: F.QuantizedTensor,
    d: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, q: jnp.ndarray,
    *, rounding: str = "stochastic", seed=0,
    strict: bool = False,
) -> Tuple[F.QuantizedTensor, jnp.ndarray]:
    """Oracle for the fused MX state-update kernel.

    The *stored* state passes through the quantizer every step (the property
    Pimba's accuracy claims rest on).  ``strict=True`` additionally quantizes
    the decayed state and the outer product before the add, emulating the
    hardware MX adder datapath (paper §5.3).
    """
    S = F.dequantize(qS)
    d_ = d.astype(jnp.float32)[..., None]
    kv = k.astype(jnp.float32)[..., None] * v.astype(jnp.float32)[..., None, :]
    if strict and qS.fmt == "mx8":
        dec = F.dequantize(F.mx8_quantize(d_ * S))
        kvq = F.dequantize(F.mx8_quantize(kv))
        Sn = dec + kvq
    else:
        Sn = d_ * S + kv
    bits = None
    if rounding == "stochastic":
        bits = F.sr_bits(Sn.shape, seed)
    qSn = F.quantize(Sn, qS.fmt, rounding, bits)
    y = jnp.einsum("bhkv,bhk->bhv", F.dequantize(qSn), q.astype(jnp.float32))
    return qSn, y


def quantized_state_update_stored_ref(
    qS: F.QuantizedTensor,
    d: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, q: jnp.ndarray,
    *, rounding: str = "stochastic", seed=0,
) -> Tuple[F.QuantizedTensor, jnp.ndarray]:
    """Oracle for the fused kernel, in the kernel's *stored* layout.

    qS holds Sᵀ with shape (B, H, dv, dk), MX groups along dk (the paper's
    dim_head-major sub-chunk layout).  Bitwise-matches the Pallas kernel.
    """
    B, H, dv, dk = qS.shape
    St = F.dequantize(qS)                                     # (B,H,dv,dk)
    d_ = jnp.broadcast_to(d.astype(jnp.float32), (B, H, dk))[:, :, None, :]
    Sn = decay_add(St, d_, v.astype(jnp.float32)[..., :, None],
                   k.astype(jnp.float32)[..., None, :])
    bits = None
    if rounding == "stochastic":
        bits = F.sr_bits(Sn.shape, seed)
    qSn = F.quantize(Sn, qS.fmt, rounding, bits)
    y = jnp.einsum("bhvk,bhk->bhv", F.dequantize(qSn), q.astype(jnp.float32))
    return qSn, y


# ---------------------------------------------------------------------------
# Decode attention over a quantized KV cache (score + attend phases)
# ---------------------------------------------------------------------------

def attention_decode_ref(
    q: jnp.ndarray,                 # (B, H, dh)
    k_cache: jnp.ndarray,           # (B, T, KVH, dk)  f32 (already dequantized)
    v_cache: jnp.ndarray,           # (B, T, KVH, dv)
    lengths: jnp.ndarray,           # (B,) valid cache lengths
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-token attention: softmax(q·Kᵀ)·V with GQA; returns (B, H, dv)."""
    B, H, dh = q.shape
    _, T, KVH, dk = k_cache.shape
    assert dh == dk
    G = H // KVH
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(B, KVH, G, dh).astype(jnp.float32)
    scores = jnp.einsum("bngd,btnd->bngt", qg, k_cache.astype(jnp.float32)) * scale
    mask = jnp.arange(T)[None, :] < lengths[:, None]          # (B, T)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngt,btnv->bngv", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, -1)


def mx_attention_decode_ref(
    q: jnp.ndarray,
    qK: F.QuantizedTensor,          # (B, T, KVH, dk) packed
    qV: F.QuantizedTensor,          # (B, T, KVH, dv) packed
    lengths: jnp.ndarray,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    return attention_decode_ref(q, F.dequantize(qK), F.dequantize(qV),
                                lengths, scale)


# ---------------------------------------------------------------------------
# MX8 quantization (host "Quantization Unit" analogue)
# ---------------------------------------------------------------------------

def mx_quantize_ref(x: jnp.ndarray, rounding: str = "nearest",
                    seed=0) -> F.QuantizedTensor:
    bits = F.sr_bits(x.shape, seed) if rounding == "stochastic" else None
    return F.mx8_quantize(x, rounding, bits)
