"""repro.ops -- the unified SPU operator subsystem.

One registry-dispatched decode-op interface for attention and state updates
(paper §4: both are the same memory-bound op class, served by one SPU).
See ``repro/ops/base.py`` for the plan/execute/traffic contract and
``repro/ops/registry.py`` for dispatch and capability negotiation.

Typical call sites::

    from repro import ops as OPS

    # state-update families (Mamba-2 / GLA / RetNet / HGRN2 / mLSTM)
    Sn, y = OPS.state_update_step(S, d, k, v, q, cfg.state_quant, seed=seed)

    # attention decode (GQA and MLA, paged and contiguous caches)
    out, cache = OPS.attention_decode_step(cache, k_new, v_new, q,
                                           cfg.state_quant, seed=seed)

    # cost models / benchmarks: the ops' own byte counts
    for entry in OPS.decode_op_plans(cfg, batch, seq_len):
        entry.traffic.state_read  # etc.
"""
# NOTE: import order matters -- base and registry first (no repro deps
# beyond core.formats), then the op implementations (which register
# themselves on import; dense before paged, the paged jnp references
# delegate to the dense ops on gathered rows), then the model-level traffic
# bridge.
from repro.ops.base import (LAYOUTS, OpPlan, SpuDeprecationWarning, SpuOp,
                            StateQuantConfig, TrafficBytes, fmt_bits,
                            fmt_of_state)
from repro.ops.platform import interpret_pallas
from repro.ops.registry import (BACKEND_PREFERENCE, OP_KINDS, backends_for,
                                execute, get_op, plan, register, registered,
                                resolve_backend, supports, traffic)
from repro.ops.state_update import (StateLike, init_state,
                                    plan_state_update,
                                    plan_state_update_dims, state_nbytes,
                                    state_update_float, state_update_step)
from repro.ops.attention import (attention_decode_step, attn_decode,
                                 attn_kind_of, kv_append,
                                 plan_attn_decode_dims)
import repro.ops.paged_ops  # noqa: F401  (registers the paged-layout ops)
from repro.ops.spec_verify import (attention_spec_step, spec_attend)
from repro.core.paged import PagedKVCache, PagedState
from repro.ops.model_traffic import (OpTrafficEntry, decode_op_plans,
                                     decode_traffic_by_kind)

__all__ = [
    "LAYOUTS", "OpPlan", "SpuDeprecationWarning", "SpuOp", "StateQuantConfig",
    "TrafficBytes", "fmt_bits", "fmt_of_state",
    "BACKEND_PREFERENCE", "OP_KINDS", "backends_for", "execute", "get_op",
    "plan", "register", "registered", "resolve_backend", "supports",
    "traffic",
    "StateLike", "init_state", "plan_state_update", "plan_state_update_dims",
    "state_nbytes", "state_update_float", "state_update_step",
    "attention_decode_step", "attn_decode", "attn_kind_of", "kv_append",
    "plan_attn_decode_dims",
    "attention_spec_step", "spec_attend",
    "PagedKVCache", "PagedState",
    "OpTrafficEntry", "decode_op_plans", "decode_traffic_by_kind",
]
