"""Pallas TPU kernel: fused MX8 state update (the SPU/SPE analogue).

One kernel invocation performs, for every (batch, head) and every dv-tile of
the state, the full Pimba SPU pipeline of paper Fig. 8:

  (1) fetch packed MX8 state tile            (HBM -> VMEM DMA)
  (2) dequantize; decay + outer product      (SPE multipliers)
  (3) add                                    (SPE adders)
  (4) requantize w/ stochastic rounding, write back, and S'ᵀq dot product

The state is logically ``(B, H, dv, dk)`` (Sᵀ) with MX groups along ``dk``
-- the analogue of the paper's layout that splits each state column along
``dim_head`` into DRAM-column-sized sub-chunks.  In this layout the output
GEMV reduces along the minor (lane) axis and the decay vector broadcasts
along it, both VPU-friendly.

Stored layout.  The kernel reads and writes the state where it lives: a
pool of slabs ``(n_slabs, L, ...)``, ``L`` stacked layers, one slab a
request.  Each of a slab-layer's payload arrays is kept lane-dense
(:func:`stored_shape`), so the TPU keeps the pool in the row-major layout
the kernel blocks and nothing is relaid out around it:

* mantissas ``(H, dv / f, f * dk)``: a head's ``(dv, dk)`` rows, ``f =
  128 / dk`` of them side by side when ``dk`` is under 128 lanes (a
  row-major reshape; :func:`fold`);
* exponent and micro bytes ``(f * dk / 16, H * dv / f)``: the group bytes
  of a stored row down a column, every head's rows along the lanes.  The
  kernel expands and packs them with the selector matmuls of
  :mod:`repro.core.formats` (``mx8_dequantize_t`` / ``mx8_quantize_t``).

One grid step updates ``rows`` heads of one batch row: their ``(dv_blk,
f * dk)`` tiles are dequantized, updated and requantized as one ``(rows *
dv_blk, f * dk)`` slab.  ``rows`` follows from the shape
(:func:`plan_blocks`): the largest divisor of ``H`` whose block fits a
fixed fast-memory budget.  The block of batch row ``b`` is addressed
straight out of ``pool[slabs[b], layer]`` (scalar prefetch), and the pool
is aliased input to output: the update is in place, mirroring the PIM
read-modify-write of the same rows.  Rows that share a slab (idle rows, on
the scratch slab 0) race; live rows own their slabs.

Pimba's access interleaving (two banks sharing one SPU so reads of bank A
overlap writes of bank B) maps to the Pallas grid pipeline: the next
block's DMA-in and the previous block's DMA-out overlap compute on the
current block via double buffering.

On the CPU the kernel runs in Pallas interpret mode, on a TPU compiled
(:mod:`repro.ops.platform` decides).  The quantization math is shared with
:mod:`repro.core.formats`, so results are bitwise equal to the pure-jnp
oracle in :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F
from repro.kernels.ref import decay_add

MXG = F.MX8_GROUP
LANES = 128


# ---------------------------------------------------------------------------
# the stored layout
# ---------------------------------------------------------------------------

def fold(dv: int, dk: int) -> int:
    """State rows one stored row holds: ``128 / dk`` for a ``dk`` under
    128 lanes that divides them (and ``dv``), else 1."""
    f = LANES // dk if dk < LANES and LANES % dk == 0 else 1
    return f if dv % f == 0 else 1


def stored_shape(field: str, h: int, dv: int, dk: int) -> Tuple[int, ...]:
    """One slab-layer's ``field`` payload as the pool stores it."""
    f = fold(dv, dk)
    if field == "mantissa":
        return (h, dv // f, f * dk)
    return (f * dk // MXG, h * dv // f)


def store(field: str, x: jnp.ndarray) -> jnp.ndarray:
    """Payload ``(..., H, dv, c)`` -> stored (:func:`stored_shape`)."""
    *lead, h, dv, c = x.shape
    dk = c if field == "mantissa" else c * MXG
    f = fold(dv, dk)
    x = x.reshape(*lead, h, dv // f, f * c)
    if field == "mantissa":
        return x
    return jnp.moveaxis(x, -1, -3).reshape(*lead, f * c, h * dv // f)


def unstore(field: str, x: jnp.ndarray, h: int, dv: int,
            dk: int) -> jnp.ndarray:
    """Inverse of :func:`store` for a state of logical ``(h, dv, dk)``."""
    f = fold(dv, dk)
    lead = x.shape[:x.ndim - (3 if field == "mantissa" else 2)]
    c = dk if field == "mantissa" else dk // MXG
    if field != "mantissa":
        x = jnp.moveaxis(x.reshape(*lead, f * c, h, dv // f), -3, -1)
    return x.reshape(*lead, h, dv, c)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _state_update_kernel(
    # scalar prefetch
    slabs_ref, layer_ref, seed_ref,
    # inputs
    mant_ref, exp_ref, micro_ref, d_ref, k_ref, v_ref, q_ref,
    # outputs
    o_mant_ref, o_exp_ref, o_micro_ref, y_ref,
    *, heads: int, dk: int, dv: int, fold: int, dv_blk: int, rows: int,
    rounding: str,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    width = fold * dk
    tile = (rows, dv_blk, width)
    slab = (rows * dv_blk, width)

    # ----- fetch + dequantize (stage 1) -----
    S = F.mx8_dequantize_t(mant_ref[...].reshape(slab), exp_ref[...],
                           micro_ref[...])                      # slab
    # per-head rows broadcast along dv, v's lanes along dk; a stored row
    # holds ``fold`` state rows, part p on lanes [p*dk, (p+1)*dk)
    d = d_ref[...][:, None, :]                                 # (rows, 1, w)
    k = k_ref[...][:, None, :]
    q = q_ref[...][:, None, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, tile, 2)
    part = lane // dk if fold > 1 else 0
    v = v_ref[0][:, :, None]                                   # (rows, blk, 1)
    for p in range(1, fold):
        v = jnp.where(part == p, v_ref[p][:, :, None], v)

    # ----- decay ∥ outer product (stage 2), update (stage 3) -----
    Sn = decay_add(S.reshape(tile), d, v, k).reshape(slab)

    # ----- requantize with stochastic rounding (LFSR analogue) -----
    bits = None
    if rounding == "stochastic":
        seed = seed_ref[0].astype(jnp.uint32)
        head = jax.lax.broadcasted_iota(jnp.int32, tile, 0) + i * rows
        srow = jax.lax.broadcasted_iota(jnp.int32, tile, 1) + j * dv_blk
        # global (b·h, dv row, dk col) index of the batch row, as the host
        # reference counts
        pair = (b * heads + head).astype(jnp.uint32)
        row, col = ((srow * fold + part, lane % dk) if fold > 1
                    else (srow, lane))
        gv = pair * jnp.uint32(dv) + row.astype(jnp.uint32)
        flat_idx = gv * jnp.uint32(dk) + col.astype(jnp.uint32)
        bits = F.counter_hash_u32(flat_idx.reshape(slab), seed)
    nm, ne, nmi = F.mx8_quantize_t(Sn, rounding, bits)

    o_mant_ref[...] = nm.reshape(tile)
    o_exp_ref[...] = ne
    o_micro_ref[...] = nmi

    # ----- output GEMV on the *stored* (requantized) state (stage 4) -----
    prod = F.mx8_dequantize_t(nm, ne, nmi).reshape(tile) * q
    if fold == 1:
        y_ref[0] = jnp.sum(prod, axis=-1)                      # (rows, blk)
    else:
        for p in range(fold):
            y_ref[p] = jnp.sum(jnp.where(part == p, prod, 0.0), axis=-1)


# Fast memory one grid step may fill: under the 16 MiB that Mosaic scopes
# by default on a v5e, with room left for the compiler's own.
VMEM_BUDGET = 12 * 1024 * 1024
# VMEM a grid step takes per state value of its block (lanes padded to
# 128): the packed state in and out, double-buffered, the per-head operands
# and the f32 temporaries of the MX8 math.  Compiled for a v5e, the kernel
# needed at most 32 B over the families' shapes (retnet's (256, 256) tiles).
BYTES_PER_VALUE = 32


def block_bytes(rows: int, dv_blk: int, width: int) -> int:
    """VMEM one grid step of ``rows`` heads' ``(dv_blk, width)`` stored
    tiles takes."""
    return BYTES_PER_VALUE * rows * dv_blk * (-(-width // LANES) * LANES)


def plan_blocks(h: int, dv: int, dk: int
                ) -> Optional[Tuple[int, int, int, Tuple[int, int]]]:
    """``(fold, dv_blk, rows, grid)`` of one batch row's ``h`` heads: each
    grid step updates ``rows`` heads' ``(dv_blk, fold * dk)`` stored tiles.

    ``rows`` is the largest divisor of ``h`` whose block of whole heads
    fits :data:`VMEM_BUDGET` and spans whole 128-lane columns of the group
    bytes (or all of them).  A head too large for the budget is cut along
    its stored rows, one head a block, into the largest 128-row tiles that
    fit.  None when neither gives a lane-aligned block (a TPU could not
    compile the kernel)."""
    f = fold(dv, dk)
    rows_s, width = dv // f, f * dk
    fits = [r for r in range(1, h + 1) if h % r == 0
            and block_bytes(r, rows_s, width) <= VMEM_BUDGET
            and (r == h or (r * rows_s) % LANES == 0)]
    if fits:
        return f, rows_s, max(fits), (h // max(fits), 1)
    tiles = [t for t in range(LANES, rows_s, LANES) if rows_s % t == 0
             and block_bytes(1, t, width) <= VMEM_BUDGET]
    if not tiles:
        return None
    return f, max(tiles), 1, (h, rows_s // max(tiles))


def _update(mant, exp, micro, slabs, layer, d, k, v, q, seed, *,
            heads: int, dv: int, dk: int, rounding: str, interpret: bool,
            in_hbm: bool):
    """One state update over stored pools ``(n, L, ...)`` in place; the
    batch row ``b`` addresses ``pool[slabs[b], layer]``.  ``in_hbm`` holds
    the pools in HBM: the compiler would otherwise prefetch a pool small
    enough into fast memory whole, every layer, for the few blocks the
    kernel reads of it."""
    B = slabs.shape[0]
    plan = plan_blocks(heads, dv, dk)
    assert plan is not None, \
        f"no lane-aligned block of {heads} heads of ({dv}, {dk})"
    f, dv_blk, rows, (nh, nt) = plan
    width = f * dk

    # per-head operands, ``rows`` heads of a block on the last two dims, so
    # any ``rows`` meets the 8 x 128 tiling; d, k and q repeat across the
    # ``f`` row parts of a stored row, v splits into them
    def per_head(x):
        x = jnp.broadcast_to(x.astype(jnp.float32), (B, heads, dk))
        return jnp.tile(x, (1, 1, f)).reshape(B, nh, rows, width)

    v = v.astype(jnp.float32).reshape(B, nh, rows, dv // f, f) \
        .transpose(0, 1, 4, 2, 3)                     # (B, nh, f, rows, dv/f)
    scalars = (slabs.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1),
               jnp.asarray(seed, jnp.int32).reshape(1))

    kernel = functools.partial(
        _state_update_kernel, heads=heads, dk=dk, dv=dv, fold=f,
        dv_blk=dv_blk, rows=rows, rounding=rounding)

    mant_spec = pl.BlockSpec(
        (None, None, rows, dv_blk, width),
        lambda b, i, j, s, l, _: (s[b], l[0], i, j, 0))
    group_spec = pl.BlockSpec(
        (None, None, width // MXG, rows * dv_blk),
        lambda b, i, j, s, l, _: (s[b], l[0], 0, i * nt + j))
    head_spec = pl.BlockSpec((None, None, rows, width),
                             lambda b, i, j, *_: (b, i, 0, 0))
    col_spec = pl.BlockSpec((None, None, f, rows, dv_blk),
                            lambda b, i, j, *_: (b, i, 0, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, nh, nt),
        in_specs=[mant_spec, group_spec, group_spec,
                  head_spec, head_spec, col_spec, head_spec],
        out_specs=[mant_spec, group_spec, group_spec, col_spec],
    )
    nm, ne, nmi, y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[(pltpu.HBM if in_hbm else jax.ShapeDtypeStruct)(
            a.shape, a.dtype) for a in (mant, exp, micro)]
        + [jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        # in-place state update: read bank / write bank of the same rows
        input_output_aliases={len(scalars) + n: n for n in range(3)},
        interpret=interpret,
        name="spu_state_update",
    )(*scalars, mant, exp, micro, per_head(d), per_head(k), v, per_head(q))
    y = y.transpose(0, 1, 3, 4, 2).reshape(B, heads, dv)
    return (nm, ne, nmi), y


def _payload(qt: F.QuantizedTensor):
    return tuple(qt.payload[f] for f in ("mantissa", "exponent", "micro"))


def _packed(shape, arrays) -> F.QuantizedTensor:
    return F.QuantizedTensor(
        "mx8", shape, dict(zip(("mantissa", "exponent", "micro"), arrays)))


@functools.partial(jax.jit, static_argnames=("rounding", "interpret"))
def mx_paged_state_update(
    pool: F.QuantizedTensor, slabs: jnp.ndarray, layer,
    d: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, q: jnp.ndarray,
    seed: jnp.ndarray, *, rounding: str = "stochastic", interpret: bool,
) -> Tuple[F.QuantizedTensor, jnp.ndarray]:
    """Fused quantized state update of the rows a slab pool holds, in place.

    Args:
      pool: packed MX8 slab pool of logical shape ``(n_slabs, L, H, dv,
        dk)``, its payload in the stored layout (:func:`stored_shape`).
      slabs: ``(B,)`` slab of each batch row; ``layer``: the ``L`` index.
      d, k, q, v, seed: as :func:`mx_state_update`.
    Returns:
      (the updated pool, y ``(B, H, dv)`` float32).
    """
    _, _, H, dv, dk = pool.shape
    assert dk % MXG == 0
    new, y = _update(*_payload(pool), slabs, layer, d, k, v, q, seed,
                     heads=H, dv=dv, dk=dk, rounding=rounding,
                     interpret=interpret, in_hbm=True)
    return _packed(pool.shape, new), y


@functools.partial(jax.jit, static_argnames=("rounding", "interpret"))
def mx_state_update(
    qS: F.QuantizedTensor,
    d: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, q: jnp.ndarray,
    seed: jnp.ndarray,
    *, rounding: str = "stochastic", interpret: bool,
) -> Tuple[F.QuantizedTensor, jnp.ndarray]:
    """Fused quantized state update.

    Args:
      qS: packed MX8 state, logical shape ``(B, H, dv, dk)``.
      d:  decay, ``(B, H, dk)`` or ``(B, H, 1)`` (broadcast for scalar decay).
      k, q: ``(B, H, dk)``;  v: ``(B, H, dv)``.
      seed: int32 scalar; vary per token step for fresh SR randomness.
    Returns:
      (new packed state, y) with y ``(B, H, dv)`` float32.

    The rows are a pool of ``B`` one-layer slabs, row ``b`` in slab ``b``,
    stored for the kernel and back.
    """
    B, H, dv, dk = qS.shape
    assert dk % MXG == 0
    fields = ("mantissa", "exponent", "micro")
    stored = [store(f, a[:, None]) for f, a in zip(fields, _payload(qS))]
    new, y = _update(*stored, jnp.arange(B, dtype=jnp.int32), 0,
                     d, k, v, q, seed, heads=H, dv=dv, dk=dk,
                     rounding=rounding, interpret=interpret, in_hbm=False)
    rows = [unstore(f, a, H, dv, dk)[:, 0] for f, a in zip(fields, new)]
    return _packed(qS.shape, rows), y
