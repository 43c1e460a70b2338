"""Pallas TPU kernel: flash-decoding attention over an MX8-packed KV cache.

Implements Pimba's attention mode (paper §5.4) as one fused kernel instead of
the paper's two-phase GPU⇄PIM handoff (score -> host softmax -> attend):

  * score phase  : q · Kᵀ on dequantized MX8 key tiles (the in-pipeline dot
                   product unit)
  * softmax      : streaming (flash) max/sum accumulators in VMEM -- on TPU
                   there is no reason to bounce partial scores to the host,
                   which removes the paper's §8 "blocked GPU/PIM" bubble
  * attend phase : probability-weighted accumulation of dequantized MX8
                   value tiles (the SPE multiplier/adder path)

GQA is handled by processing all G = H / KV_heads query heads of a KV head
together against each KV tile (operand reuse across the chunk group, the
analogue of Pimba broadcasting shared operands once per chunk group).  One
grid step reads the tile of *every* KV head of a row: the kernel reads the
cache as ``(..., KVH*d, t)``, tokens on the lanes, so that a tile is
lane-dense at any head width (a 160-wide head's ten exponent bytes would
pad to 128 lanes with the tokens on the rows).

MLA mode (DeepSeek-V2): the cache is a single compressed latent stream; the
same tiles serve as keys (full width) and values (first ``v_width`` rows),
so no V operand is passed -- the kernel reads the K refs for both phases.

:func:`flash_decode` is the one kernel body and ``pallas_call`` shared by
dense decode (here), paged decode (:mod:`repro.kernels.mx_paged_attention`)
and speculative verify (:mod:`repro.kernels.mx_spec_attention`).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F

NEG_INF = -1e30


def _payload(qt: F.QuantizedTensor) -> Tuple[jnp.ndarray, ...]:
    return qt.payload["mantissa"], qt.payload["exponent"], qt.payload["micro"]


#: exponent rows dequantized together: 32 of them (512 value rows) keep
#: every slice of a u8 block on its 32-row tile
_EXP_CHUNK = 32


def time_minor(qt: F.QuantizedTensor) -> F.QuantizedTensor:
    """Dense ``(B, T, KVH, w)`` payloads -> ``(B, KVH*w, T)``: the layout
    :func:`flash_decode` reads, tokens on the lanes (the paged pools are
    stored so)."""
    def tm(a):
        return jnp.swapaxes(a.reshape(a.shape[:2] + (-1,)), 1, 2)
    return F.QuantizedTensor(qt.fmt, qt.shape,
                             {f: tm(a) for f, a in qt.payload.items()})


def _dequant_tile(refs3, lead, scr):
    """One tile's MX8 payload refs ``(R, t)``, ``(R/16, t)`` x 2 -> f32
    ``scr`` ``(R, t)``, a chunk of exponent rows at a time."""
    mant_ref, exp_ref, micro_ref = refs3
    n_exp = exp_ref.shape[-2]
    ch = _EXP_CHUNK if n_exp % _EXP_CHUNK == 0 else n_exp
    rows = ch * F.MX8_GROUP
    for c in range(n_exp // ch):
        e = exp_ref[lead + (pl.ds(c * ch, ch), slice(None))]
        m = micro_ref[lead + (pl.ds(c * ch, ch), slice(None))]
        mant = mant_ref[lead + (pl.ds(c * rows, rows), slice(None))]
        scr[pl.ds(c * rows, rows), :] = F.mx8_dequantize_rows(mant, e, m)


def _flash_kernel(*refs, n_prefetch: int, paged: bool, mla: bool, t_blk: int,
                  n_t: int, n_q: int, g: int, dk: int, dv: int):
    """One KV tile of streaming-softmax attention for every KV head of a row.

    The query block is ``(KVH, n_q*g, dk)``: query row ``r`` belongs to
    draft position ``r // g`` and sees positions ``< len - (n_q-1 - r//g)``
    (``n_q == 1`` is plain decode).  The tile holds every head's rows,
    tokens on the lanes (``(KVH*dk, t)``); it is dequantized at once into
    VMEM scratch, and a loop over heads then runs the flash update on each
    head's ``(dk, t)`` rows.
    """
    lens_ref = refs[n_prefetch - 1]
    q_ref, *rest = refs[n_prefetch:]
    n_kv = 3 if mla else 6
    kv_refs, (y_ref, m_scr, l_scr, acc_scr, *kv_scr) = (rest[:n_kv],
                                                         rest[n_kv:])
    b, t = pl.program_id(0), pl.program_id(1)
    length = lens_ref[b]
    n_heads = q_ref.shape[1]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lead = (0, 0) if paged else (0,)
    for refs3, scr in zip((kv_refs[:3], kv_refs[3:]), kv_scr):
        _dequant_tile(refs3, lead, scr)

    def rows(h, w):
        return pl.ds(pl.multiple_of(h * w, 8) if w % 8 == 0 else h * w, w)

    def head(h, carry):
        kt = kv_scr[0][rows(h, dk), :]                         # (dk, t_blk)
        vt = kt[:dv] if mla else kv_scr[1][rows(h, dv), :]     # (dv, t_blk)
        qv = q_ref[0, h].astype(jnp.float32)                   # (n_q*g, dk)
        scores = jax.lax.dot_general(
            qv, kt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (n_q*g, t_blk)
        pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + t * t_blk
        qidx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // g
        valid = pos < length - (n_q - 1 - qidx)
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_scr[h]                                      # (n_q*g, 1)
        l_prev = l_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, vt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (n_q*g, dv)
        m_scr[h] = m_new
        l_scr[h] = l_new
        acc_scr[h] = acc_scr[h] * alpha + pv
        return carry

    jax.lax.fori_loop(0, n_heads, head, 0)

    @pl.when(t == n_t - 1)
    def _finish():
        y_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def flash_decode(
    qg: jnp.ndarray,                     # (B, KVH, n_q*g, dk) scaled queries
    k: F.QuantizedTensor,                # dense (B, KVH*dk, T) | pool (P, G, KVH*dk, 128)
    v: Optional[F.QuantizedTensor],      # like k; None => MLA
    lengths: jnp.ndarray,                # (B,) valid length incl. the n_q rows
    *, n_q: int, v_width: int, interpret: bool, name: str,
    t_block: int = 128, pages: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Shared flash-decoding ``pallas_call``; returns (B, KVH, n_q*g, dv).

    The payloads hold tokens on the last axis (:func:`time_minor`), every
    head's rows stacked before it, so a tile is lane-dense at any head
    width.  Dense caches tile the time axis in ``t_block`` steps.  With
    ``pages = (bt, group)`` the caches are page pools and the grid's time
    axis walks the scalar-prefetched block table ``bt[B, npg]`` instead,
    one 128-token page per step, so no dense copy of the context exists.
    ``name`` is the kernel's name in the compiled program (its SPU op kind).
    """
    B, KVH, QG, dk = qg.shape
    mla = v is None
    lens = lengths.astype(jnp.int32).reshape(B)
    parts: Sequence[jnp.ndarray] = _payload(k) + (() if mla else _payload(v))
    if pages is None:
        T = k.payload["mantissa"].shape[-1]
        assert T % t_block == 0
        n_t = T // t_block
        prefetch = (lens,)
        kv_map = lambda b, t, *_: (b, 0, t)
        kv_block = lambda r: (1, r, t_block)
    else:
        bt, group = pages
        t_block, n_t = k.payload["mantissa"].shape[-1], int(bt.shape[1])
        prefetch = (bt, jnp.asarray(group, jnp.int32).reshape(1), lens)
        kv_map = lambda b, t, bt_ref, g_ref, _: (bt_ref[b, t], g_ref[0], 0, 0)
        kv_block = lambda r: (1, 1, r, t_block)
    dv = v_width if mla else v.payload["mantissa"].shape[-2] // KVH

    kernel = functools.partial(
        _flash_kernel, n_prefetch=len(prefetch), paged=pages is not None,
        mla=mla, t_blk=t_block, n_t=n_t, n_q=n_q, g=QG // n_q, dk=dk, dv=dv)
    row_map = lambda b, t, *_: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_t),
        in_specs=[pl.BlockSpec((1, KVH, QG, dk), row_map)]
        + [pl.BlockSpec(kv_block(a.shape[-2]), kv_map) for a in parts],
        out_specs=pl.BlockSpec((1, KVH, QG, dv), row_map),
        scratch_shapes=[
            pltpu.VMEM((KVH, QG, 1), jnp.float32),
            pltpu.VMEM((KVH, QG, 1), jnp.float32),
            pltpu.VMEM((KVH, QG, dv), jnp.float32),
        ] + [pltpu.VMEM((parts[i].shape[-2], t_block), jnp.float32)
             for i in ((0,) if mla else (0, 3))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, QG, dv), jnp.float32),
        interpret=interpret,
        name=name,
    )(*prefetch, qg.astype(jnp.float32), *parts)


@functools.partial(
    jax.jit,
    static_argnames=("t_block", "interpret", "v_width", "scale"),
)
def mx_attention_decode(
    q: jnp.ndarray,                 # (B, H, dk) current-token queries
    qK: F.QuantizedTensor,          # (B, T, KVH, dk) packed keys
    qV: Optional[F.QuantizedTensor],  # (B, T, KVH, dv) packed values; None => MLA
    lengths: jnp.ndarray,           # (B,) int32 valid cache length
    *, scale: Optional[float] = None, v_width: Optional[int] = None,
    t_block: int = 128, interpret: bool,
) -> jnp.ndarray:
    """Fused decode attention; returns (B, H, dv) f32."""
    B, H, dk = q.shape
    KVH = qK.shape[2]
    assert dk == qK.shape[3] and H % KVH == 0
    assert qV is not None or v_width is not None
    scale = scale if scale is not None else dk ** -0.5
    qg = (q.astype(jnp.float32) * scale).reshape(B, KVH, H // KVH, dk)
    y = flash_decode(qg, time_minor(qK),
                     None if qV is None else time_minor(qV), lengths, n_q=1,
                     v_width=v_width, t_block=t_block, interpret=interpret,
                     name="spu_attn_decode")
    return y.reshape(B, H, -1)
