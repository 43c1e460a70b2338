"""Pallas TPU kernel: fused MX8 state update (the SPU/SPE analogue).

One kernel invocation performs, for every (batch, head) and every dv-tile of
the state, the full Pimba SPU pipeline of paper Fig. 8:

  (1) fetch packed MX8 state tile            (HBM -> VMEM DMA)
  (2) dequantize; decay + outer product      (SPE multipliers)
  (3) add                                    (SPE adders)
  (4) requantize w/ stochastic rounding, write back, and S'ᵀq dot product

The state is *stored* transposed, ``(B, H, dv, dk)`` with MX groups along
``dk`` -- the analogue of the paper's layout that splits each state column
along ``dim_head`` into DRAM-column-sized sub-chunks.  In this layout the
output GEMV reduces along the minor (lane) axis and the decay vector
broadcasts along it, both VPU-friendly.

Pimba's access interleaving (two banks sharing one SPU so reads of bank A
overlap writes of bank B) maps to the Pallas grid pipeline: the next tile's
DMA-in and the previous tile's DMA-out overlap compute on the current tile
via double buffering.  ``input_output_aliases`` keeps the update in place,
mirroring the PIM read-modify-write of the same rows.

On the CPU the kernel runs in Pallas interpret mode, on a TPU compiled
(:mod:`repro.ops.platform` decides).  The quantization math is shared with
:mod:`repro.core.formats`, so results are bitwise equal to the pure-jnp
oracle in :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import formats as F
from repro.kernels.ref import decay_add

MXG = F.MX8_GROUP


def _dequant_tile(mant, exp, micro):
    """(R, C) int8 mantissas + per-group exponent/micro bytes -> f32."""
    qt = F.QuantizedTensor("mx8", mant.shape,
                           {"mantissa": mant, "exponent": exp, "micro": micro})
    return F.mx8_dequantize(qt)


def _quant_tile(x, rounding, bits):
    qt = F.mx8_quantize(x, rounding, bits)
    return qt.payload["mantissa"], qt.payload["exponent"], qt.payload["micro"]


def _state_update_kernel(
    # inputs
    seed_ref, mant_ref, exp_ref, micro_ref, d_ref, k_ref, v_ref, q_ref,
    # outputs
    o_mant_ref, o_exp_ref, o_micro_ref, y_ref,
    *, dk: int, dv: int, dv_blk: int, rounding: str,
):
    bh = pl.program_id(0)
    j = pl.program_id(1)

    # ----- fetch + dequantize (stage 1) -----
    S = _dequant_tile(mant_ref[0], exp_ref[0], micro_ref[0])   # (dv_blk, dk)
    d = d_ref[0]                                               # (1, dk)
    k = k_ref[0]                                               # (1, dk)
    q = q_ref[0]                                               # (1, dk)
    v = v_ref[0]                                               # (dv_blk, 1)

    # ----- decay ∥ outer product (stage 2), update (stage 3) -----
    Sn = decay_add(S, d, v, k)                                 # (dv_blk, dk)

    # ----- requantize with stochastic rounding (LFSR analogue) -----
    bits = None
    if rounding == "stochastic":
        seed = seed_ref[0, 0].astype(jnp.uint32)
        row = jax.lax.broadcasted_iota(jnp.uint32, (dv_blk, dk), 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, (dv_blk, dk), 1)
        gv = bh.astype(jnp.uint32) * jnp.uint32(dv) \
            + jnp.uint32(j * dv_blk) + row                      # global dv index
        flat = gv * jnp.uint32(dk) + col
        bits = F.counter_hash_u32(flat, seed)
    nm, ne, nmi = _quant_tile(Sn, rounding, bits)
    o_mant_ref[0] = nm
    o_exp_ref[0] = ne
    o_micro_ref[0] = nmi

    # ----- output GEMV on the *stored* (requantized) state (stage 4) -----
    Snq = _dequant_tile(nm, ne, nmi)
    y_ref[0] = jnp.sum(Snq * q, axis=-1, keepdims=True)        # (dv_blk, 1)


def _pick_dv_block(dv: int) -> int:
    for cand in (256, 128, 64, 32, 16):
        if dv % cand == 0:
            return min(cand, dv)
    raise ValueError(f"dv={dv} must be a multiple of 16")


@functools.partial(
    jax.jit,
    static_argnames=("rounding", "interpret", "dv_block"),
)
def mx_state_update(
    qS: F.QuantizedTensor,
    d: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, q: jnp.ndarray,
    seed: jnp.ndarray,
    *, rounding: str = "stochastic", interpret: bool,
    dv_block: int | None = None,
) -> Tuple[F.QuantizedTensor, jnp.ndarray]:
    """Fused quantized state update.

    Args:
      qS: packed MX8 state, logical shape ``(B, H, dv, dk)`` (stored layout).
      d:  decay, ``(B, H, dk)`` or ``(B, H, 1)`` (broadcast for scalar decay).
      k, q: ``(B, H, dk)``;  v: ``(B, H, dv)``.
      seed: int32 scalar; vary per token step for fresh SR randomness.
    Returns:
      (new packed state, y) with y ``(B, H, dv)`` float32.
    """
    B, H, dv, dk = qS.shape
    assert dk % MXG == 0
    dv_blk = dv_block or _pick_dv_block(dv)
    assert dv % dv_blk == 0
    n_tiles = dv // dv_blk
    BH = B * H

    mant = qS.payload["mantissa"].reshape(BH, dv, dk)
    exp = qS.payload["exponent"].reshape(BH, dv, dk // MXG)
    micro = qS.payload["micro"].reshape(BH, dv, dk // MXG)
    # per-head operands as (1, dk) rows and (dv, 1) columns: TPU blocks
    # must span the last two array dims (or 8 x 128 multiples of them)
    d = jnp.broadcast_to(d.astype(jnp.float32), (B, H, dk)).reshape(BH, 1, dk)
    k = k.astype(jnp.float32).reshape(BH, 1, dk)
    q = q.astype(jnp.float32).reshape(BH, 1, dk)
    v = v.astype(jnp.float32).reshape(BH, dv, 1)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    grid = (BH, n_tiles)
    kernel = functools.partial(
        _state_update_kernel, dk=dk, dv=dv, dv_blk=dv_blk, rounding=rounding)

    out_shapes = [
        jax.ShapeDtypeStruct((BH, dv, dk), jnp.int8),
        jax.ShapeDtypeStruct((BH, dv, dk // MXG), jnp.uint8),
        jax.ShapeDtypeStruct((BH, dv, dk // MXG), jnp.uint8),
        jax.ShapeDtypeStruct((BH, dv, 1), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),                      # seed
        pl.BlockSpec((1, dv_blk, dk), lambda i, j: (i, j, 0)),          # mant
        pl.BlockSpec((1, dv_blk, dk // MXG), lambda i, j: (i, j, 0)),   # exp
        pl.BlockSpec((1, dv_blk, dk // MXG), lambda i, j: (i, j, 0)),   # micro
        pl.BlockSpec((1, 1, dk), lambda i, j: (i, 0, 0)),               # d
        pl.BlockSpec((1, 1, dk), lambda i, j: (i, 0, 0)),               # k
        pl.BlockSpec((1, dv_blk, 1), lambda i, j: (i, j, 0)),           # v
        pl.BlockSpec((1, 1, dk), lambda i, j: (i, 0, 0)),               # q
    ]
    out_specs = [
        pl.BlockSpec((1, dv_blk, dk), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, dv_blk, dk // MXG), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, dv_blk, dk // MXG), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, dv_blk, 1), lambda i, j: (i, j, 0)),
    ]

    nm, ne, nmi, y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        # in-place state update: read bank / write bank of the same rows
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
        name="spu_state_update",
    )(seed_arr, mant, exp, micro, d, k, v, q)

    qSn = F.QuantizedTensor("mx8", qS.shape, {
        "mantissa": nm.reshape(B, H, dv, dk),
        "exponent": ne.reshape(B, H, dv, dk // MXG),
        "micro": nmi.reshape(B, H, dv, dk // MXG),
    })
    return qSn, y.reshape(B, H, dv)
