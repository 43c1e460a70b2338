"""Low-precision numeric formats for state / KV-cache quantization.

Implements the formats studied in Pimba §3.2 / §4.2 (paper Figs. 4 and 6):

* ``mx8``      -- Microsoft MX, 8-bit average: groups of 16 values share an
                  8-bit exponent, pairs of values share a 1-bit micro-exponent,
                  each value stores sign + 6-bit mantissa.  The Pareto-optimal
                  format chosen by the paper.
* ``int8``     -- 8-bit integer with a per-32-element scale (the "GPU+Q"
                  baseline format).
* ``fp8_e4m3`` / ``fp8_e5m2`` -- 8-bit floats (shown by the paper to suffer
                  from swamping in state-update workloads).
* ``fp16`` / ``bf16`` / ``fp32`` -- reference formats.

Each format supports round-to-nearest-even and stochastic rounding (SR).
SR consumes caller-supplied uniform uint32 bits so that the host path and the
Pallas kernel path (which generates bits with the same counter-based hash,
see :func:`counter_hash_u32`) are bit-compatible and reproducible.

All quantization groups run along the **last** axis of the input.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Format registry
# ---------------------------------------------------------------------------

MX8_GROUP = 16          # values per shared exponent
MX8_PAIR = 2            # values per micro-exponent
MX8_MBITS = 6           # mantissa magnitude bits (sign stored separately)
INT8_GROUP = 32         # values per scale in the int8-scaled format

FORMATS = ("fp32", "bf16", "fp16", "fp8_e4m3", "fp8_e5m2", "int8", "mx8")
ROUNDINGS = ("nearest", "stochastic")

#: average storage bits per value, used for memory/bandwidth accounting.
FORMAT_BITS: Dict[str, float] = {
    "fp32": 32.0,
    "bf16": 16.0,
    "fp16": 16.0,
    "fp8_e4m3": 8.0,
    "fp8_e5m2": 8.0,
    # 8 bits + fp16 scale per 32 values
    "int8": 8.0 + 16.0 / INT8_GROUP,
    # sign+6b mantissa + 8b exponent / 16 + 1b microexponent / 2
    "mx8": (1 + MX8_MBITS) + 8.0 / MX8_GROUP + 1.0 / MX8_PAIR,
}

_FP8_MAX = {"fp8_e4m3": 448.0, "fp8_e5m2": 57344.0}
_FP8_MBITS = {"fp8_e4m3": 3, "fp8_e5m2": 2}
_FP8_EMIN = {"fp8_e4m3": -6, "fp8_e5m2": -14}   # min normal exponent
_FP8_DTYPE = {"fp8_e4m3": jnp.float8_e4m3fn, "fp8_e5m2": jnp.float8_e5m2}

#: bias applied to the stored MX group exponent (uint8).
MX8_EXP_BIAS = 127


# ---------------------------------------------------------------------------
# QuantizedTensor pytree
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class QuantizedTensor:
    """An opaque quantized array.  ``payload`` holds format-specific parts."""

    fmt: str
    shape: tuple
    payload: Dict[str, jnp.ndarray]

    def tree_flatten_with_keys(self):
        keys = tuple(sorted(self.payload))
        children = [(jax.tree_util.DictKey(k), self.payload[k]) for k in keys]
        return children, (self.fmt, self.shape, keys)

    @classmethod
    def tree_unflatten(cls, aux, children):
        fmt, shape, keys = aux
        return cls(fmt, shape, dict(zip(keys, children)))

    @property
    def nbytes_logical(self) -> float:
        """Logical storage bytes (as a real packed implementation would use)."""
        n = float(np.prod(self.shape))
        return n * FORMAT_BITS[self.fmt] / 8.0


# ---------------------------------------------------------------------------
# Random bits for stochastic rounding
# ---------------------------------------------------------------------------

def counter_hash_u32(counter: jnp.ndarray, seed) -> jnp.ndarray:
    """Counter-based stateless PRNG ("lowbias32" integer hash).

    This is the software analogue of Pimba's per-SPE LFSR: cheap, stateless,
    and identical between the host reference path and the Pallas kernels (it
    uses only elementwise uint32 ops, so it lowers to the TPU VPU directly).
    """
    x = counter.astype(jnp.uint32) ^ (jnp.uint32(seed) * jnp.uint32(0x9E3779B9))
    x ^= x >> 16
    x *= jnp.uint32(0x7FEB352D)
    x ^= x >> 15
    x *= jnp.uint32(0x846CA68B)
    x ^= x >> 16
    return x


def sr_bits(shape, seed, offset=0) -> jnp.ndarray:
    """Uniform uint32 bits for SR over an array of ``shape``."""
    n = int(np.prod(shape))
    idx = jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(offset)
    return counter_hash_u32(idx, seed).reshape(shape)


def _u32_to_unit(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> uniform in [0, 1)."""
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & 0xFFFF).astype(jnp.int32).astype(jnp.float32)
    return (hi * jnp.float32(65536.0) + lo) * jnp.float32(2.0 ** -32)


def _round(x: jnp.ndarray, rounding: str, bits: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Round float values to integers with RNE or SR."""
    if rounding == "nearest":
        return jnp.round(x)  # round-half-to-even
    if bits is None:
        raise ValueError("stochastic rounding requires random bits")
    return jnp.floor(x + _u32_to_unit(bits))


# ---------------------------------------------------------------------------
# MX8
# ---------------------------------------------------------------------------

def _frexp_exponent(x: jnp.ndarray) -> jnp.ndarray:
    """e such that 2^(e-1) <= x < 2^e for normal x>0 (0 -> very small exponent).

    Implemented by exponent-field extraction (not ``jnp.frexp``) so the exact
    same integer ops run inside Pallas kernels and on the host -- this is what
    makes kernel-vs-reference comparisons bitwise, and it is also how the
    hardware exponent unit works.
    """
    raw = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    e = ((raw >> 23) & 0xFF) - 126
    return jnp.where(x > 0, e, -MX8_EXP_BIAS + 1).astype(jnp.int32)


# The MX8 math below keeps every value on its own lane: the group and pair
# reductions are xor-butterflies over the last axis (``jnp.roll`` + select),
# and the per-group bytes move between lanes and groups through 0/1 selector
# matmuls on small integers (exact in bf16).  No reshape splits the last
# axis, so the same functions lower inside the TPU kernels, whose compiler
# refuses lane-splitting reshapes, and on the host.

def _lane_partner(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """``x[..., i ^ k]`` along the last axis, for ``k`` a power of two."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((lane & k) == 0, jnp.roll(x, -k, axis=-1),
                     jnp.roll(x, k, axis=-1))


def _pair_index(shape) -> jnp.ndarray:
    """Pair position (0..7) of every lane inside its MX group."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane % MX8_GROUP) // MX8_PAIR


def _group_selector(n: int, stride: int = 1,
                    groups_first: bool = False) -> jnp.ndarray:
    """(n, n/16) 0/1 matrix selecting, for group g, its lanes ``l`` with
    ``l % stride == 0``; ``(n/16, n)`` with ``groups_first``."""
    lane_ax = 1 if groups_first else 0
    shape = (n, n // MX8_GROUP)[::-1 if groups_first else 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, lane_ax)
    group = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - lane_ax)
    return ((lane // MX8_GROUP == group)
            & (lane % stride == 0)).astype(jnp.bfloat16)


def _small_int_dot(a: jnp.ndarray, b: jnp.ndarray, dims) -> jnp.ndarray:
    """``dot_general`` over the contracting ``dims`` of two small-integer
    operands, one of them a 0/1 selector and the other in 0..255: every
    product and sum is an integer below 256, so bf16 inputs with f32
    accumulation are exact."""
    bf16 = lambda x: x.astype(jnp.float32).astype(jnp.bfloat16)
    out = jax.lax.dot_general(
        bf16(a), bf16(b), (dims, ((), ())),
        precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def _small_int_matmul(x: jnp.ndarray, sel: jnp.ndarray) -> jnp.ndarray:
    """Contract the last axis of integer ``x`` with a 0/1 selector."""
    return _small_int_dot(x, sel, ((x.ndim - 1,), (0,)))


def _pow2(k: jnp.ndarray) -> jnp.ndarray:
    """``2.0 ** k`` in f32 for integer ``k`` in [-149, 127], built from its
    bit pattern: exact on every backend (XLA:CPU's vectorized ``exp2`` is
    not, and an inexact scale would make the quantizer path-dependent)."""
    k = k.astype(jnp.int32)
    normal = jax.lax.bitcast_convert_type(
        jnp.left_shift(jnp.maximum(k + 127, 1), 23), jnp.float32)
    subnormal = jax.lax.bitcast_convert_type(
        jnp.left_shift(1, jnp.clip(k + 149, 0, 22)), jnp.float32)
    return jnp.where(k >= -126, normal, subnormal)


def _mx8_encode(x: jnp.ndarray, rounding: str,
                bits: Optional[jnp.ndarray]):
    """Mantissas of ``x`` along its last axis, plus on every lane its
    group's biased exponent and its micro-exponent bit shifted to its pair
    position (a group's sum of those is its packed micro byte)."""
    n = x.shape[-1]
    assert n % MX8_GROUP == 0, f"last dim {n} not divisible by {MX8_GROUP}"
    xf = x.astype(jnp.float32)
    pmax = jnp.abs(xf)
    pmax = jnp.maximum(pmax, _lane_partner(pmax, 1))           # pair max
    gmax = pmax
    for k in (2, 4, 8):
        gmax = jnp.maximum(gmax, _lane_partner(gmax, k))       # group max
    e = _frexp_exponent(gmax)                                  # shared exponent
    e = jnp.clip(e, -MX8_EXP_BIAS + 1, 127)

    # micro-exponent: 1 => pair magnitudes fit in half the group range, so we
    # can shift the pair scale down one binade and gain a mantissa bit.
    micro = (pmax < _pow2(e - 1)).astype(jnp.int32)
    scale = _pow2(e - MX8_MBITS - micro)
    q = _round(xf / scale, rounding, bits)
    mant = jnp.clip(q, -63, 63).astype(jnp.int8)
    return (mant, e + MX8_EXP_BIAS,
            jnp.left_shift(micro, _pair_index(x.shape)))


def mx8_quantize(x: jnp.ndarray, rounding: str = "nearest",
                 bits: Optional[jnp.ndarray] = None) -> QuantizedTensor:
    """Quantize to MX8 along the last axis (length must divide MX8_GROUP)."""
    n = x.shape[-1]
    mant, e, micro = _mx8_encode(x, rounding, bits)
    # one lane per group carries the exponent; one lane per pair its bit,
    # shifted into place so the group's sum is its packed micro byte
    exp_stored = _small_int_matmul(e, _group_selector(n, MX8_GROUP))
    micro_packed = _small_int_matmul(micro, _group_selector(n, MX8_PAIR))
    return QuantizedTensor("mx8", x.shape, {
        "mantissa": mant, "exponent": exp_stored.astype(jnp.uint8),
        "micro": micro_packed.astype(jnp.uint8),
    })


def mx8_quantize_t(x: jnp.ndarray, rounding: str = "nearest",
                   bits: Optional[jnp.ndarray] = None):
    """:func:`mx8_quantize` of a 2-D ``(N, n)`` ``x``, its group bytes
    returned transposed: ``(mantissa (N, n), exponent (n/16, N), micro
    (n/16, N))``, the groups down the rows and ``x``'s rows on the lanes.
    The same bytes, as the state-update kernel stores them."""
    n = x.shape[-1]
    mant, e, micro = _mx8_encode(x, rounding, bits)
    contract = ((1,), (1,))
    exp_t = _small_int_dot(_group_selector(n, MX8_GROUP, True), e, contract)
    micro_t = _small_int_dot(_group_selector(n, MX8_PAIR, True), micro,
                             contract)
    return mant, exp_t.astype(jnp.uint8), micro_t.astype(jnp.uint8)


def mx8_dequantize_t(mant: jnp.ndarray, exp_t: jnp.ndarray,
                     micro_t: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`mx8_quantize_t`: ``mant`` ``(N, n)``, ``exp_t``
    and ``micro_t`` ``(n/16, N)``.  The same values as
    :func:`mx8_dequantize` of the untransposed payload."""
    sel = _group_selector(mant.shape[-1], groups_first=True)     # (G, n)
    contract = ((0,), (0,))
    e = _small_int_dot(exp_t.astype(jnp.int32), sel, contract) - MX8_EXP_BIAS
    mp = _small_int_dot(micro_t.astype(jnp.int32), sel, contract)
    micro = (mp >> _pair_index(mant.shape)) & 1
    return mant.astype(jnp.float32) * _pow2(e - MX8_MBITS - micro)


def mx8_dequantize(qt: QuantizedTensor) -> jnp.ndarray:
    mant = qt.payload["mantissa"].astype(jnp.float32)
    n = qt.shape[-1]
    expand = _group_selector(n).T                                 # (G, n)
    e = _small_int_matmul(qt.payload["exponent"].astype(jnp.int32),
                          expand) - MX8_EXP_BIAS
    mp = _small_int_matmul(qt.payload["micro"].astype(jnp.int32), expand)
    micro = (mp >> _pair_index(mant.shape)) & 1
    scale = _pow2(e - MX8_MBITS - micro)
    return (mant * scale).reshape(qt.shape)


def mx8_dequantize_rows(mant: jnp.ndarray, exp: jnp.ndarray,
                        micro: jnp.ndarray) -> jnp.ndarray:
    """Dequantize an MX8 payload whose groups run down the rows.

    ``mant`` is ``(16 n, t)``, ``exp`` and ``micro`` ``(n, t)``: row ``r``
    belongs to group ``r // 16``, as in the paged K/V pools, which keep a
    page's tokens on the lanes.  The same values as :func:`mx8_dequantize`
    of the transposed payload."""
    n, t = exp.shape
    sel = _group_selector(MX8_GROUP * n)                          # (16n, n)

    def expand(x):
        return jax.lax.dot_general(
            sel, x.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32).astype(jnp.int32)

    e = expand(exp) - MX8_EXP_BIAS
    row = jax.lax.broadcasted_iota(jnp.int32, (MX8_GROUP * n, t), 0)
    micro = (expand(micro) >> ((row % MX8_GROUP) // MX8_PAIR)) & 1
    return mant.astype(jnp.float32) * _pow2(e - MX8_MBITS - micro)


# ---------------------------------------------------------------------------
# int8 with per-group scale
# ---------------------------------------------------------------------------

def int8_quantize(x: jnp.ndarray, rounding: str = "nearest",
                  bits: Optional[jnp.ndarray] = None) -> QuantizedTensor:
    orig_shape = x.shape
    n = x.shape[-1]
    assert n % INT8_GROUP == 0, f"last dim {n} not divisible by {INT8_GROUP}"
    xf = x.astype(jnp.float32)
    g = xf.reshape(*x.shape[:-1], n // INT8_GROUP, INT8_GROUP)
    gmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    scale = jnp.where(gmax > 0, gmax / 127.0, 1.0)
    q = g / scale
    if bits is not None:
        bits = bits.reshape(g.shape)
    q = jnp.clip(_round(q, rounding, bits), -127, 127).astype(jnp.int8)
    return QuantizedTensor("int8", orig_shape, {
        "q": q.reshape(*x.shape[:-1], n),
        "scale": scale.squeeze(-1).astype(jnp.float16),
    })


def int8_dequantize(qt: QuantizedTensor) -> jnp.ndarray:
    q = qt.payload["q"].astype(jnp.float32)
    scale = qt.payload["scale"].astype(jnp.float32)
    n = qt.shape[-1]
    g = q.reshape(*q.shape[:-1], n // INT8_GROUP, INT8_GROUP)
    return (g * scale[..., None]).reshape(qt.shape)


# ---------------------------------------------------------------------------
# fp8 (emulated)
# ---------------------------------------------------------------------------

def _fp8_quantize_values(x: jnp.ndarray, fmt: str, rounding: str,
                         bits: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Returns fp8 values stored as their own dtype."""
    xf = x.astype(jnp.float32)
    fmax = _FP8_MAX[fmt]
    xf = jnp.clip(xf, -fmax, fmax)
    if rounding == "nearest":
        return xf.astype(_FP8_DTYPE[fmt])
    # Stochastic rounding: snap to the ulp grid of the target format, then the
    # exact cast is value-preserving.
    mbits = _FP8_MBITS[fmt]
    _, e = jnp.frexp(xf)
    e = jnp.where(xf != 0, e, _FP8_EMIN[fmt])
    # exponent of the representable binade: 2^(e-1) <= |x| < 2^e
    ulp_exp = jnp.maximum(e - 1, _FP8_EMIN[fmt]) - mbits
    ulp = jnp.exp2(ulp_exp.astype(jnp.float32))
    q = jnp.floor(xf / ulp + _u32_to_unit(bits)) * ulp
    q = jnp.clip(q, -fmax, fmax)
    return q.astype(_FP8_DTYPE[fmt])


def fp8_quantize(x: jnp.ndarray, fmt: str, rounding: str = "nearest",
                 bits: Optional[jnp.ndarray] = None) -> QuantizedTensor:
    return QuantizedTensor(fmt, x.shape,
                           {"x": _fp8_quantize_values(x, fmt, rounding, bits)})


# ---------------------------------------------------------------------------
# Unified entry points
# ---------------------------------------------------------------------------

def quantize(x: jnp.ndarray, fmt: str, rounding: str = "nearest",
             bits: Optional[jnp.ndarray] = None) -> QuantizedTensor:
    """Quantize ``x`` (groups along the last axis) into ``fmt``."""
    if fmt == "mx8":
        return mx8_quantize(x, rounding, bits)
    if fmt == "int8":
        return int8_quantize(x, rounding, bits)
    if fmt in _FP8_DTYPE:
        return fp8_quantize(x, fmt, rounding, bits)
    if fmt in ("fp16", "bf16"):
        dt = jnp.float16 if fmt == "fp16" else jnp.bfloat16
        return QuantizedTensor(fmt, x.shape, {"x": x.astype(dt)})
    if fmt == "fp32":
        return QuantizedTensor(fmt, x.shape, {"x": x.astype(jnp.float32)})
    raise ValueError(f"unknown format {fmt!r}")


def dequantize(qt: QuantizedTensor) -> jnp.ndarray:
    if qt.fmt == "mx8":
        return mx8_dequantize(qt)
    if qt.fmt == "int8":
        return int8_dequantize(qt)
    return qt.payload["x"].astype(jnp.float32)


def quantize_like(x: jnp.ndarray, qt: QuantizedTensor, rounding: str = "nearest",
                  bits: Optional[jnp.ndarray] = None) -> QuantizedTensor:
    return quantize(x, qt.fmt, rounding, bits)


# ---------------------------------------------------------------------------
# "Strict" MX arithmetic (paper §5.3 adder/multiplier semantics)
# ---------------------------------------------------------------------------
# Pimba's SPE computes directly on MX operands with shift-aligned integer
# add/multiply.  On TPU we compute in f32 between MX8 load/store (see
# DESIGN.md §2); the functions below emulate the *stricter* hardware
# semantics -- every intermediate re-enters MX8 -- for the accuracy study.

def strict_mx_add(a: jnp.ndarray, b: jnp.ndarray, rounding: str = "nearest",
                  bits: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(quantize(a) + quantize(b)) requantized: models the MX adder path."""
    s = dequantize(mx8_quantize(a)) + dequantize(mx8_quantize(b))
    return dequantize(mx8_quantize(s, rounding, bits))


def strict_mx_mul(a: jnp.ndarray, b: jnp.ndarray, rounding: str = "nearest",
                  bits: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    p = dequantize(mx8_quantize(a)) * dequantize(mx8_quantize(b))
    return dequantize(mx8_quantize(p, rounding, bits))
