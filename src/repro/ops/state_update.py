"""The generalized state-update operator (paper Eq. 2) as registered SpuOps.

    S_t = d_t ⊙ S_{t-1} + k_t v_tᵀ ;   y_t = S_tᵀ q_t

Storage layout for the resident state is ``(B, H, dv, dk)`` (Sᵀ) with MX
groups along dk; see ``kernels/mx_state_update.py`` for why.  Two backends:

* ``pallas`` -- the fused kernel (interpreted on the CPU, compiled on a
  TPU; :mod:`repro.ops.platform` decides).  MX8 only.
* ``jnp``    -- mathematically identical pure-jnp path for every storage
  format (bitwise identical packed state for MX8).  This is what the
  multi-pod dry-run lowers: interpret-mode pallas would trace its grid as an
  unrolled Python loop and distort cost analysis.

The plan/execute/traffic split (see ``repro.ops.base``) keeps the cost
models honest: ``traffic(plan)`` is *the* byte count for an invocation --
``core/pimsim.py`` and ``analysis/roofline.py`` consume it directly.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple, Union

import jax.numpy as jnp

from repro.core import formats as F
from repro.kernels import ref as _ref
from repro.kernels.mx_state_update import (mx_state_update as _su_pallas,
                                           plan_blocks)
from repro.ops import registry
from repro.ops.base import (OPERAND_BYTES, OUTPUT_BYTES, OpPlan, SpuOp,
                            StateQuantConfig, TrafficBytes, fmt_bits,
                            fmt_of_state)
from repro.ops.platform import interpret_pallas

StateLike = Union[F.QuantizedTensor, jnp.ndarray]

_FLOAT_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "fp16": jnp.float16}


# ---------------------------------------------------------------------------
# state containers
# ---------------------------------------------------------------------------

def init_state(B: int, H: int, dk: int, dv: int,
               cfg: StateQuantConfig) -> StateLike:
    """Zero-initialized recurrent state, stored layout (B, H, dv, dk)."""
    zeros = jnp.zeros((B, H, dv, dk), jnp.float32)
    if not cfg.quantized:
        return zeros.astype(_FLOAT_DTYPES[cfg.fmt])
    return F.quantize(zeros, cfg.fmt)


def state_nbytes(B: int, H: int, dk: int, dv: int, cfg: StateQuantConfig) -> float:
    """Logical storage bytes of one layer's state (bandwidth accounting)."""
    p = plan_state_update_dims(B, H, dk, dv, cfg)
    return registry.traffic(p).state_read


# ---------------------------------------------------------------------------
# op implementations
# ---------------------------------------------------------------------------

class _StateUpdateBase(SpuOp):
    kind = "state_update"

    def traffic(self, plan: OpPlan) -> TrafficBytes:
        B, H = plan.dim("B"), plan.dim("H")
        dk, dv = plan.dim("dk"), plan.dim("dv")
        state = B * H * dk * dv * plan.bits_per_val / 8.0
        # d/k/q are (B,H,dk), v is (B,H,dv); y is (B,H,dv) f32
        operands = B * H * (3 * dk + dv) * OPERAND_BYTES
        out = B * H * dv * OUTPUT_BYTES
        return TrafficBytes(state_read=state, state_write=state,
                            operand_read=operands, output_write=out)


@registry.register
class StateUpdatePallas(_StateUpdateBase):
    """Fused MX8 state update (quant + decay + outer + GEMV in one kernel)."""
    backend = "pallas"
    formats = ("mx8",)

    def execute(self, state, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[StateLike, jnp.ndarray]:
        if plan_blocks(*state.shape[1:]) is None:
            # no lane-aligned block of such heads: the reference
            return registry.get_op("state_update", "jnp", plan.fmt).execute(
                state, inputs, plan)
        return _su_pallas(state, inputs["d"], inputs["k"], inputs["v"],
                          inputs["q"],
                          jnp.asarray(inputs.get("seed", 0), jnp.int32),
                          rounding=plan.rounding,
                          interpret=interpret_pallas())


@registry.register
class StateUpdateJnp(_StateUpdateBase):
    """Pure-jnp reference semantics for every storage format."""
    backend = "jnp"
    formats = ("mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp32", "bf16", "fp16")

    def execute(self, state, inputs: Dict[str, Any],
                plan: OpPlan) -> Tuple[StateLike, jnp.ndarray]:
        d, k, v, q = inputs["d"], inputs["k"], inputs["v"], inputs["q"]
        seed = inputs.get("seed", 0)
        if not isinstance(state, F.QuantizedTensor):
            return state_update_float(state, d, k, v, q, dtype=state.dtype)
        if state.fmt == "mx8":
            return _ref.quantized_state_update_stored_ref(
                state, d, k, v, q, rounding=plan.rounding, seed=seed)
        # int8 / fp8 paths: dequant -> update -> requant reference semantics
        B, H, dv, dk = state.shape
        St = F.dequantize(state)
        d_ = jnp.broadcast_to(d.astype(jnp.float32), (B, H, dk))[:, :, None, :]
        Sn = St * d_ + (v.astype(jnp.float32)[..., :, None]
                        * k.astype(jnp.float32)[..., None, :])
        bits = (F.sr_bits(Sn.shape, seed)
                if plan.rounding == "stochastic" else None)
        qSn = F.quantize(Sn, state.fmt, plan.rounding, bits)
        y = jnp.einsum("bhvk,bhk->bhv", F.dequantize(qSn), q.astype(jnp.float32))
        return qSn, y


def state_update_float(S: jnp.ndarray, d, k, v, q,
                       dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unquantized baseline (the paper's "GPU" fp16 configuration).

    State layout (B, H, dv, dk) to match the quantized path.
    """
    St = S.astype(jnp.float32)
    d_ = jnp.broadcast_to(d.astype(jnp.float32), St.shape[:2] + St.shape[-1:])
    Sn = St * d_[:, :, None, :] + (v.astype(jnp.float32)[..., :, None]
                                   * k.astype(jnp.float32)[..., None, :])
    y = jnp.einsum("bhvk,bhk->bhv", Sn, q.astype(jnp.float32))
    return Sn.astype(dtype), y


# ---------------------------------------------------------------------------
# call-site entry points
# ---------------------------------------------------------------------------

def plan_state_update_dims(B: int, H: int, dk: int, dv: int,
                           cfg: StateQuantConfig, *, layout: str = "dense",
                           strict: bool = False) -> OpPlan:
    """Plan one Eq. 2 invocation from explicit dims (cost-model entry)."""
    return registry.plan("state_update", dict(B=B, H=H, dk=dk, dv=dv),
                         cfg, cfg.backend, layout=layout, strict=strict)


def plan_state_update(state, cfg: StateQuantConfig) -> OpPlan:
    """Plan from a live state container; format and layout come from the
    container (a ``PagedState`` slab view dispatches the paged op, which
    updates the owned slab rows in place)."""
    from repro.core.paged import PagedState
    if isinstance(state, PagedState):
        B, H, dv, dk = state.shape
        quant = StateQuantConfig(fmt=state.fmt, rounding=cfg.rounding,
                                 backend=cfg.backend)
        return plan_state_update_dims(B, H, dk, dv, quant, layout="paged")
    B, H, dv, dk = state.shape
    quant = StateQuantConfig(fmt=fmt_of_state(state), rounding=cfg.rounding,
                             backend=cfg.backend)
    return plan_state_update_dims(B, H, dk, dv, quant)


def state_update_step(state: StateLike, d: jnp.ndarray, k: jnp.ndarray,
                      v: jnp.ndarray, q: jnp.ndarray, cfg: StateQuantConfig,
                      seed=0) -> Tuple[StateLike, jnp.ndarray]:
    """One decode step of Eq. 2: plan + dispatch through the registry.

    d: (B,H,dk) or (B,H,1); k,q: (B,H,dk); v: (B,H,dv)  ->  y: (B,H,dv) f32.
    """
    p = plan_state_update(state, cfg)
    return registry.execute(state, {"d": d, "k": k, "v": v, "q": q,
                                    "seed": seed}, p)
