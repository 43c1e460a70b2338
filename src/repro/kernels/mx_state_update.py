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

One grid step updates a block of ``rows`` (batch, head) pairs: their
``(rows, dv_blk, dk)`` tiles are dequantized, updated and requantized as one
``(rows * dv_blk, dk)`` slab, so the per-step costs (prologue, block DMAs,
the MXU fill of each selector matmul) are paid once a block, not once a
pair.  ``rows`` follows from the shape (:func:`plan_blocks`): the largest
divisor of ``B * H`` whose block fits a fixed fast-memory budget.  The
per-pair operands are lane-dense 2-D rows of ``rows`` pairs; moving ``v``
from lanes to sublanes happens in VMEM.

Pimba's access interleaving (two banks sharing one SPU so reads of bank A
overlap writes of bank B) maps to the Pallas grid pipeline: the next
block's DMA-in and the previous block's DMA-out overlap compute on the
current block via double buffering.  ``input_output_aliases`` keeps the
update in place, mirroring the PIM read-modify-write of the same rows.

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
    """(N, C) mantissas + per-group exponent/micro bytes -> f32."""
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
    *, dk: int, dv: int, dv_blk: int, rows: int, rounding: str,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    tile = (rows, dv_blk, dk)
    slab = (rows * dv_blk, dk)

    def flat(x):                      # (rows, dv_blk, c) -> (rows*dv_blk, c)
        return x.reshape(slab[0], x.shape[-1])

    # ----- fetch + dequantize (stage 1) -----
    S = _dequant_tile(flat(mant_ref[...]), flat(exp_ref[...]),
                      flat(micro_ref[...]))                    # slab
    # per-pair rows broadcast along dv, v's lanes along dk
    d = d_ref[...][:, None, :]                                 # (rows, 1, dk)
    k = k_ref[...][:, None, :]
    q = q_ref[...][:, None, :]
    v = v_ref[...][:, :, None]                                 # (rows, blk, 1)

    # ----- decay ∥ outer product (stage 2), update (stage 3) -----
    Sn = decay_add(S.reshape(tile), d, v, k).reshape(slab)

    # ----- requantize with stochastic rounding (LFSR analogue) -----
    bits = None
    if rounding == "stochastic":
        seed = seed_ref[0, 0].astype(jnp.uint32)
        pair = jax.lax.broadcasted_iota(jnp.int32, tile, 0) + i * rows
        row = jax.lax.broadcasted_iota(jnp.int32, tile, 1) + j * dv_blk
        col = jax.lax.broadcasted_iota(jnp.int32, tile, 2)
        # global (b·h, dv row, dk col) index, as the host reference counts
        gv = pair.astype(jnp.uint32) * jnp.uint32(dv) + row.astype(jnp.uint32)
        flat_idx = gv * jnp.uint32(dk) + col.astype(jnp.uint32)
        bits = F.counter_hash_u32(flat_idx.reshape(slab), seed)
    nm, ne, nmi = _quant_tile(Sn, rounding, bits)

    o_mant_ref[...] = nm.reshape(tile)
    o_exp_ref[...] = ne.reshape(rows, dv_blk, ne.shape[-1])
    o_micro_ref[...] = nmi.reshape(rows, dv_blk, nmi.shape[-1])

    # ----- output GEMV on the *stored* (requantized) state (stage 4) -----
    Snq = _dequant_tile(nm, ne, nmi)
    y_ref[...] = jnp.sum(Snq.reshape(tile) * q, axis=-1)      # (rows, dv_blk)


def _pick_dv_block(dv: int) -> int:
    for cand in (256, 128, 64, 32, 16):
        if dv % cand == 0:
            return min(cand, dv)
    raise ValueError(f"dv={dv} must be a multiple of 16")


# Fast memory one grid step may fill: under the 16 MiB that Mosaic scopes
# by default on a v5e, with room left for the compiler's own.
VMEM_BUDGET = 12 * 1024 * 1024
# VMEM a grid step takes per state value of its block (lanes padded to
# 128): the packed state in and out, double-buffered, the per-pair operands
# and the f32 temporaries of the MX8 math.  Compiled for a v5e, the kernel
# needed at most 32 B over the families' shapes (retnet's (256, 256) tiles).
BYTES_PER_VALUE = 32


def block_bytes(rows: int, dv_blk: int, dk: int) -> int:
    """VMEM one grid step of ``rows`` (row, head) pairs takes."""
    return BYTES_PER_VALUE * rows * dv_blk * (-(-dk // 128) * 128)


def plan_blocks(bh: int, dv: int, dk: int, dv_block: int | None = None
                ) -> Tuple[int, int, Tuple[int, int]]:
    """``(dv_blk, rows, grid)`` of one call over ``bh`` (row, head) pairs:
    each grid step updates ``rows`` pairs' ``(dv_blk, dk)`` tiles, ``rows``
    the largest divisor of ``bh`` whose block fits :data:`VMEM_BUDGET`."""
    dv_blk = dv_block or _pick_dv_block(dv)
    assert dv % dv_blk == 0
    rows = max((r for r in range(1, bh + 1) if bh % r == 0
                and block_bytes(r, dv_blk, dk) <= VMEM_BUDGET), default=1)
    return dv_blk, rows, (bh // rows, dv // dv_blk)


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
    BH = B * H
    dv_blk, rows, grid = plan_blocks(BH, dv, dk, dv_block)
    nb, n_tiles = grid
    G = dk // MXG

    mant = qS.payload["mantissa"].reshape(BH, dv, dk)
    exp = qS.payload["exponent"].reshape(BH, dv, G)
    micro = qS.payload["micro"].reshape(BH, dv, G)
    # per-pair operands, lane-dense: one block of ``rows`` pairs spans the
    # last two dims of its array, so any ``rows`` meets the 8 x 128 tiling
    d = jnp.broadcast_to(d.astype(jnp.float32), (B, H, dk)).reshape(
        nb, rows, dk)
    k = k.astype(jnp.float32).reshape(nb, rows, dk)
    q = q.astype(jnp.float32).reshape(nb, rows, dk)
    v = v.astype(jnp.float32).reshape(nb, rows, n_tiles, dv_blk) \
        .transpose(0, 2, 1, 3)                        # (nb, tiles, rows, blk)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    kernel = functools.partial(
        _state_update_kernel, dk=dk, dv=dv, dv_blk=dv_blk, rows=rows,
        rounding=rounding)

    out_shapes = [
        jax.ShapeDtypeStruct((BH, dv, dk), jnp.int8),
        jax.ShapeDtypeStruct((BH, dv, G), jnp.uint8),
        jax.ShapeDtypeStruct((BH, dv, G), jnp.uint8),
        jax.ShapeDtypeStruct((nb, n_tiles, rows, dv_blk), jnp.float32),
    ]
    state = lambda c: pl.BlockSpec((rows, dv_blk, c), lambda i, j: (i, j, 0))
    pair = pl.BlockSpec((None, rows, dk), lambda i, j: (i, 0, 0))
    col = pl.BlockSpec((None, None, rows, dv_blk), lambda i, j: (i, j, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),      # seed
        state(dk), state(G), state(G),                  # mant, exp, micro
        pair, pair, col, pair,                          # d, k, v, q
    ]
    out_specs = [state(dk), state(G), state(G), col]

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
        "exponent": ne.reshape(B, H, dv, G),
        "micro": nmi.reshape(B, H, dv, G),
    })
    y = y.transpose(0, 2, 1, 3).reshape(B, H, dv)
    return qSn, y
