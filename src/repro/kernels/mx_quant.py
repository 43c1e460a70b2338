"""Pallas kernel: MX8 quantizer (the host memory-controller "Quantization
Unit" of paper §5.5 REG_WRITE).  Streams f32/bf16 rows and emits packed MX8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import formats as F

MXG = F.MX8_GROUP


def _quant_kernel(seed_ref, x_ref, m_ref, e_ref, mi_ref, *,
                  cols: int, r_blk: int, rounding: str):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (r_blk, cols)
    bits = None
    if rounding == "stochastic":
        seed = seed_ref[0, 0].astype(jnp.uint32)
        row = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
        flat = (i.astype(jnp.uint32) * jnp.uint32(r_blk) + row) * jnp.uint32(cols) + col
        bits = F.counter_hash_u32(flat, seed)
    qt = F.mx8_quantize(x, rounding, bits)
    m_ref[...] = qt.payload["mantissa"]
    e_ref[...] = qt.payload["exponent"]
    mi_ref[...] = qt.payload["micro"]


@functools.partial(jax.jit, static_argnames=("rounding", "interpret", "row_block"))
def mx_quantize(x: jnp.ndarray, seed=0, *, rounding: str = "nearest",
                row_block: int = 64, interpret: bool) -> F.QuantizedTensor:
    """Quantize a 2D-reshapeable array to MX8 (groups along the last axis)."""
    orig_shape = x.shape
    cols = x.shape[-1]
    assert cols % MXG == 0
    rows = int(x.size // cols)
    x2 = x.reshape(rows, cols)
    r_blk = min(row_block, rows)
    # pad rows to a block multiple
    pad = (-rows) % r_blk
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n_blk = x2.shape[0] // r_blk
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    kernel = functools.partial(_quant_kernel, cols=cols, r_blk=r_blk,
                               rounding=rounding)
    m, e, mi = pl.pallas_call(
        kernel,
        grid=(n_blk,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((r_blk, cols), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((r_blk, cols), lambda i: (i, 0)),
            pl.BlockSpec((r_blk, cols // MXG), lambda i: (i, 0)),
            pl.BlockSpec((r_blk, cols // MXG), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x2.shape[0], cols), jnp.int8),
            jax.ShapeDtypeStruct((x2.shape[0], cols // MXG), jnp.uint8),
            jax.ShapeDtypeStruct((x2.shape[0], cols // MXG), jnp.uint8),
        ],
        interpret=interpret,
        name="spu_mx_quantize",
    )(seed_arr, x2)

    if pad:
        m, e, mi = m[:rows], e[:rows], mi[:rows]
    gshape = orig_shape[:-1] + (cols // MXG,)
    return F.QuantizedTensor("mx8", orig_shape, {
        "mantissa": m.reshape(orig_shape),
        "exponent": e.reshape(gshape),
        "micro": mi.reshape(gshape),
    })
