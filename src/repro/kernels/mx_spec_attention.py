"""Pallas TPU kernels: speculative-verify attention over MX8 KV caches.

Speculative decoding verifies ``Kq`` drafted tokens in one pass: the cache
already holds the ``Kq`` appended rows, and query position ``j`` attends over
every position strictly before ``lengths - (Kq-1-j)`` (its own row included).
``Kq == 1`` degenerates exactly to the plain decode kernels.

Both kernels run the flash score -> streaming-softmax -> attend pipeline of
:func:`repro.kernels.mx_attention.flash_decode` (dense, or paged over the
block table) by folding the query axis into the GQA group axis: the query
block becomes ``(Kq*G, dk)`` and the VMEM accumulators ``(Kq*G, .)``, so
every query row keeps its own private max/sum/acc lane.  Row-wise the
arithmetic is identical to running the single-query kernel once per position
with the per-position length -- which is what makes greedy speculative
decode bit-identical to sequential decode.

The bandwidth story (paper §3): the K/V pages stream through the grid ONCE
for all ``Kq`` queries -- the verify pass re-reads the same bytes one decode
step does, amortized over the drafted tokens.  That is the whole reason
speculation is nearly free in the memory-bound decode regime.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import formats as F
from repro.core.paged import PAGE_TOKENS
from repro.kernels.mx_attention import flash_decode, time_minor


def _fold_queries(q: jnp.ndarray, KVH: int, scale: float) -> jnp.ndarray:
    """(B, Kq, H, dk) -> (B, KVH, Kq*G, dk) with query-major row order."""
    B, Kq, H, dk = q.shape
    G = H // KVH
    qg = (q.astype(jnp.float32) * scale).reshape(B, Kq, KVH, G, dk)
    return jnp.transpose(qg, (0, 2, 1, 3, 4)).reshape(B, KVH, Kq * G, dk)


def _unfold_outputs(y: jnp.ndarray, Kq: int) -> jnp.ndarray:
    """(B, KVH, Kq*G, dv) -> (B, Kq, H, dv)."""
    B, KVH, QG, dv = y.shape
    G = QG // Kq
    y = y.reshape(B, KVH, Kq, G, dv)
    return jnp.transpose(y, (0, 2, 1, 3, 4)).reshape(B, Kq, KVH * G, dv)


@functools.partial(
    jax.jit, static_argnames=("t_block", "interpret", "v_width", "scale"))
def mx_spec_attention_decode(
    q: jnp.ndarray,                 # (B, Kq, H, dk) verify-position queries
    qK: F.QuantizedTensor,          # (B, T, KVH, dk) packed keys
    qV: Optional[F.QuantizedTensor],  # packed values; None => MLA
    lengths: jnp.ndarray,           # (B,) valid length INCLUDING the Kq rows
    *, scale: Optional[float] = None, v_width: Optional[int] = None,
    t_block: int = 128, interpret: bool,
) -> jnp.ndarray:
    """Fused dense spec-verify attention; returns (B, Kq, H, dv) f32."""
    B, Kq, H, dk = q.shape
    KVH = qK.shape[2]
    assert dk == qK.shape[3] and H % KVH == 0
    assert qV is not None or v_width is not None
    scale = scale if scale is not None else dk ** -0.5
    y = flash_decode(_fold_queries(q, KVH, scale), time_minor(qK),
                     None if qV is None else time_minor(qV), lengths, n_q=Kq,
                     v_width=v_width, t_block=t_block, interpret=interpret,
                     name="spu_spec_verify")
    return _unfold_outputs(y, Kq)


@functools.partial(
    jax.jit, static_argnames=("interpret", "v_width", "scale"))
def mx_paged_spec_attention_decode(
    q: jnp.ndarray,                 # (B, Kq, H, dk)
    k_pool: F.QuantizedTensor,      # pools of logical shape (P, G, 128, KVH, dk)
    v_pool: Optional[F.QuantizedTensor],  # like k_pool; None => MLA
    bt: jnp.ndarray,                # (B, npg) int32 physical page ids
    group,                          # () int32 stacked-layer index
    lengths: jnp.ndarray,           # (B,) valid length INCLUDING the Kq rows
    *, scale: Optional[float] = None, v_width: Optional[int] = None,
    interpret: bool,
) -> jnp.ndarray:
    """Fused paged spec-verify attention; returns (B, Kq, H, dv) f32.

    The pages stream through the grid once for all ``Kq`` queries: the grid
    is the same ``(B, npg)`` as the single-query paged kernel, only the
    query block and the VMEM accumulators widen by ``Kq``.
    """
    B, Kq, H, dk = q.shape
    P, G, TB, KVH, dkc = k_pool.shape
    assert dk == dkc and H % KVH == 0 and TB == PAGE_TOKENS
    assert v_pool is not None or v_width is not None
    scale = scale if scale is not None else dk ** -0.5
    y = flash_decode(_fold_queries(q, KVH, scale), k_pool, v_pool, lengths,
                     n_q=Kq, v_width=v_width, pages=(bt, group),
                     interpret=interpret, name="spu_spec_verify")
    return _unfold_outputs(y, Kq)
