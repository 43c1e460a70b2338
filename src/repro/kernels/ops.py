"""DEPRECATED shim -- kernel entry points moved to the ``repro.ops`` registry.

The ``backend=`` keyword dispatch that used to live here is now capability
negotiation in ``repro/ops/registry.py`` (op kind x backend x format), and
the implementations are registered SpuOps in ``repro/ops/state_update.py``
and ``repro/ops/attention.py``.  These wrappers keep external scripts
working: they emit :class:`~repro.ops.base.SpuDeprecationWarning` and
forward to the registry, returning bit-identical results.
"""
from __future__ import annotations

import warnings
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core import formats as F
from repro.ops.base import SpuDeprecationWarning, StateQuantConfig

DEFAULT_BACKEND = "pallas"


def _warn(old: str, new: str):
    warnings.warn(f"repro.kernels.ops.{old} is deprecated; use {new}",
                  SpuDeprecationWarning, stacklevel=3)


def state_update(
    qS: F.QuantizedTensor,
    d: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, q: jnp.ndarray,
    seed, *, rounding: str = "stochastic", backend: str = DEFAULT_BACKEND,
) -> Tuple[F.QuantizedTensor, jnp.ndarray]:
    """Deprecated: use repro.ops.state_update_step."""
    _warn("state_update", "repro.ops.state_update_step")
    from repro import ops as OPS
    cfg = StateQuantConfig(fmt=qS.fmt, rounding=rounding, backend=backend)
    return OPS.state_update_step(qS, d, k, v, q, cfg, seed=seed)


def state_update_float(S: jnp.ndarray, d, k, v, q,
                       dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deprecated: use repro.ops.state_update_float."""
    _warn("state_update_float", "repro.ops.state_update_float")
    from repro.ops.state_update import state_update_float as _f
    return _f(S, d, k, v, q, dtype=dtype)


def attention_decode(
    q: jnp.ndarray,
    qK: F.QuantizedTensor, qV: Optional[F.QuantizedTensor],
    lengths: jnp.ndarray,
    *, scale: Optional[float] = None, v_width: Optional[int] = None,
    t_block: int = 128, backend: str = DEFAULT_BACKEND,
) -> jnp.ndarray:
    """Deprecated: use repro.ops.attn_decode on a KVCache."""
    _warn("attention_decode", "repro.ops.attn_decode")
    from repro.core.attention_cache import KVCache
    from repro.ops.attention import attn_decode
    cache = KVCache(qK, qV, lengths, qK.fmt, v_width)
    cfg = StateQuantConfig(fmt=qK.fmt, rounding="nearest", backend=backend)
    return attn_decode(cache, q, cfg, scale=scale, t_block=t_block)


def quantize_mx8(x: jnp.ndarray, seed=0, *, rounding: str = "nearest",
                 backend: str = DEFAULT_BACKEND) -> F.QuantizedTensor:
    """Deprecated: use repro.core.formats.quantize / kernels.mx_quant."""
    _warn("quantize_mx8", "repro.core.formats.quantize")
    if backend == "pallas":
        from repro.kernels.mx_quant import mx_quantize as _quant_pallas
        from repro.ops.platform import interpret_pallas
        return _quant_pallas(x, seed, rounding=rounding,
                             interpret=interpret_pallas())
    from repro.kernels import ref as _ref
    return _ref.mx_quantize_ref(x, rounding=rounding, seed=seed)
