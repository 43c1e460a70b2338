"""State-update mixers: Mamba-2, GLA, RetNet, HGRN2, mLSTM, sLSTM.

All of these share the generalized state-update decode step (paper Eq. 2,
the ``state_update`` SPU op in repro.ops).  Training/prefill run in the "compute-intensive
form" the paper assigns to the GPU: a chunked linear-attention formulation
(the SSD duality of Dao & Gu) that is MXU-friendly -- quadratic within small
chunks, recurrent across chunks.

Two chunked engines cover every family member:
  * scalar per-step decay (Mamba-2 dt·a, RetNet γ_h, mLSTM sigmoid-f)
  * vector per-step decay  (GLA per-channel gates, HGRN2 forget gates)

Decode routes every family through ONE registered SPU op invocation
(:func:`_spu_state_update` -> ``repro.ops.state_update_step``); what differs
per family is only the decay/gating hook that produces Eq. 2's d_t
(``_DECAY_HOOKS``) and the pre/post projections around the op.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops as OPS
from repro.core import formats as F
from repro.models import layers as L
from repro.models.config import ModelConfig

Params = dict
MixerState = Dict[str, object]


def _spu_state_update(state, decay, k, v, q, cfg: ModelConfig, seed):
    """The one decode-time Eq. 2 invocation shared by every state family.

    Dispatches through the SPU op registry (kind ``state_update``, backend
    negotiated from ``cfg.state_quant``); see repro/ops/state_update.py.
    """
    return OPS.state_update_step(state, decay, k, v, q, cfg.state_quant,
                                 seed=seed)


#: per-family decode decay hooks: log-decay (as produced by the shared qkv
#: projections) -> Eq. 2 d_t.  Scalar families feed (B,H,1); vector-gated
#: families feed the per-channel (B,H,dk) gate.
_DECAY_HOOKS = {
    "gla": lambda log_f: jnp.exp(log_f[:, :, 0]),          # (B,H,dk)
    "hgrn2": lambda log_f: jnp.exp(log_f[:, :, 0]),        # (B,H,dk)
    "retnet": lambda log_f: jnp.exp(log_f[..., :1]),       # (B,H,1)
    "mamba2": lambda log_f: jnp.exp(log_f),                # (B,H,1)
    "mlstm": lambda log_f: jnp.exp(log_f),                 # (B,H,1)
}


# ---------------------------------------------------------------------------
# chunked linear attention engines
# ---------------------------------------------------------------------------

def chunked_la_scalar(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      log_a: jnp.ndarray, chunk: int, unroll: bool = False,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scalar-decay chunked scan.

    q, k: (B,H,S,dk); v: (B,H,S,dv); log_a: (B,H,S) per-step log decay (<=0).
    Returns y: (B,H,S,dv) and the final state (B,H,dk,dv) in f32.
    """
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, S)
    S0_len = S
    pad = (-S) % c
    if pad:  # zero tokens with decay 1 leave the state untouched
        zpad = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3))
        q, k, v, log_a = zpad(q), zpad(k), zpad(v), zpad(log_a)
        S = S + pad
    nc = S // c

    def to_chunks(x, feat):
        x = x.reshape(B, H, nc, c, *feat)
        return jnp.moveaxis(x, 2, 0)               # (nc, B, H, c, ...)

    # keep q/k/v in their storage dtype (bf16 in production); the decay
    # factors and accumulators are f32.  Full-sequence f32 copies of q/k/v
    # would dominate training-step memory.
    qc = to_chunks(q, (dk,))
    kc = to_chunks(k, (dk,))
    vc = to_chunks(v, (dv,))
    la = to_chunks(log_a.astype(jnp.float32), ())

    cum = jnp.cumsum(la, axis=-1)                  # (nc,B,H,c) inclusive
    total = cum[..., -1:]

    tril = jnp.tril(jnp.ones((c, c), bool))

    def body(S_prev, inp):
        qi, ki, vi, cumi, toti = inp
        # intra-chunk quadratic part
        dmat = jnp.exp(cumi[..., :, None] - cumi[..., None, :])  # (B,H,c,c)
        A = jnp.einsum("bhcd,bhed->bhce", qi, ki,
                       preferred_element_type=jnp.float32) * dmat
        A = jnp.where(tril, A, 0.0)
        y = jnp.einsum("bhce,bhev->bhcv", A.astype(vi.dtype), vi,
                       preferred_element_type=jnp.float32)
        # inter-chunk contribution from the carried state
        q_in = (qi.astype(jnp.float32) * jnp.exp(cumi)[..., None]).astype(qi.dtype)
        y = y + jnp.einsum("bhcd,bhdv->bhcv", q_in, S_prev.astype(qi.dtype),
                           preferred_element_type=jnp.float32)
        # state recurrence to the chunk end
        k_end = (ki.astype(jnp.float32)
                 * jnp.exp(toti - cumi)[..., None]).astype(ki.dtype)
        S_next = jnp.exp(toti)[..., None] * S_prev + jnp.einsum(
            "bhcd,bhcv->bhdv", k_end, vi, preferred_element_type=jnp.float32)
        return S_next, y

    S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    S_fin, yc = jax.lax.scan(body, S0, (qc, kc, vc, cum, total),
                             unroll=unroll)
    y = jnp.moveaxis(yc, 0, 2).reshape(B, H, S, dv)[:, :, :S0_len]
    return y, S_fin


def chunked_la_vector(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      log_f: jnp.ndarray, chunk: int, unroll: bool = False,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vector-decay chunked scan (GLA / HGRN2).

    log_f: (B,H,S,dk) per-channel log decay, clamped >= cfg.log_decay_min by
    the caller so exp(-cum) stays finite within a chunk.
    """
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, S)
    S0_len = S
    pad = (-S) % c
    if pad:
        zpad = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3))
        q, k, v, log_f = zpad(q), zpad(k), zpad(v), zpad(log_f)
        S = S + pad
    nc = S // c

    def to_chunks(x):
        return jnp.moveaxis(x.reshape(B, H, nc, c, -1), 2, 0)

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    lf = to_chunks(log_f.astype(jnp.float32))
    cum = jnp.cumsum(lf, axis=-2)                  # (nc,B,H,c,dk)
    total = cum[..., -1:, :]

    tril = jnp.tril(jnp.ones((c, c), bool))

    def body(S_prev, inp):
        qi, ki, vi, cumi, toti = inp
        q_in = qi.astype(jnp.float32) * jnp.exp(cumi)
        k_de = ki.astype(jnp.float32) * jnp.exp(-cumi)   # bounded by the clamp
        A = jnp.einsum("bhcd,bhed->bhce", q_in, k_de)
        A = jnp.where(tril, A, 0.0)
        y = jnp.einsum("bhce,bhev->bhcv", A.astype(vi.dtype), vi,
                       preferred_element_type=jnp.float32)
        y = y + jnp.einsum("bhcd,bhdv->bhcv", q_in, S_prev)
        k_end = ki.astype(jnp.float32) * jnp.exp(toti - cumi)
        S_next = jnp.exp(toti[..., 0, :, None]) * S_prev + jnp.einsum(
            "bhcd,bhcv->bhdv", k_end, vi.astype(jnp.float32))
        return S_next, y

    S0 = jnp.zeros((B, H, dk, dv), jnp.float32)
    S_fin, yc = jax.lax.scan(body, S0, (qc, kc, vc, cum, total),
                             unroll=unroll)
    y = jnp.moveaxis(yc, 0, 2).reshape(B, H, S, dv)[:, :, :S0_len]
    return y, S_fin


def shard_heads(x: jnp.ndarray, par) -> jnp.ndarray:
    """Constrain (B, H, S, ...) per-head activations for the chunk engines.

    Two jobs: (1) shard H (or the feature dim when H doesn't divide TP, e.g.
    xLSTM's 4 giant heads) over 'model' so head-shared broadcasts don't
    materialize TP-replicated; (2) pin the SEQUENCE dim unsharded -- the
    chunked scans reshape S into (nc, c) and slice per step, and slicing a
    sharded dim triggers involuntary full resharding every iteration."""
    if par is None or not hasattr(par, "mesh"):
        return x
    B, H = x.shape[:2]
    if B % par.batch_size_divisor != 0:
        return x
    from jax.sharding import PartitionSpec as P
    dims = [par.batch_axes] + [None] * (x.ndim - 1)
    if H % par.tp == 0:
        dims[1] = par.model_axis
    # else: batch-only.  Sharding the feature dim instead (xLSTM's 4 giant
    # heads) makes every chunk-scan einsum a cross-step partitioning puzzle
    # (measured: pathological SPMD compile); H-indivisible mixers replicate
    # over TP -- an inherent limit of 4-head architectures, noted in
    # DESIGN.md §Arch-applicability.
    return jax.lax.with_sharding_constraint(x, par.named(P(*dims)))


def _store_state(S_logical: jnp.ndarray, cfg: ModelConfig) -> OPS.StateLike:
    """(B,H,dk,dv) f32 -> stored container (B,H,dv,dk)."""
    St = jnp.swapaxes(S_logical, -1, -2)
    sq = cfg.state_quant
    if not sq.quantized:
        dt = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
              "fp16": jnp.float16}[sq.fmt]
        return St.astype(dt)
    return F.quantize(St, sq.fmt)


# ---------------------------------------------------------------------------
# causal depthwise conv (mamba2 / mlstm front conv)
# ---------------------------------------------------------------------------

def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """x: (B,S,C), w: (d_conv, C): y_t = sum_i w_i * x_{t-d_conv+1+i} + b."""
    d_conv = w.shape[0]
    out = jnp.zeros_like(x)
    for i in range(d_conv):
        shift = d_conv - 1 - i
        xi = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]]
        out = out + xi * w[i]
    return out + b


def causal_conv_step(x_new: jnp.ndarray, conv_state: jnp.ndarray,
                     w: jnp.ndarray, b: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token conv step.  x_new: (B,C); conv_state: (B,d_conv-1,C)."""
    win = jnp.concatenate([conv_state, x_new[:, None]], axis=1)  # (B,d_conv,C)
    y = jnp.einsum("bdc,dc->bc", win, w) + b
    return y, win[:, 1:]


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------

def _m2_dims(cfg: ModelConfig):
    sc = cfg.ssm
    d_inner = sc.expand * cfg.d_model
    H = d_inner // sc.head_dim
    return d_inner, H, sc.d_state, sc.head_dim


def init_mamba2(key, cfg: ModelConfig) -> Params:
    """Projections are kept as separate matrices (wz/wx/wbc/wdt) rather than
    one fused in_proj so each gets a uniform TP sharding: z/x shard over the
    head (model) axis, B/C are head-shared and replicate, dt shards over H."""
    d = cfg.d_model
    d_inner, H, N, P = _m2_dims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    return {
        "wz": L.dense_init(ks[0], d, d_inner, dt),
        "wx": L.dense_init(ks[1], d, d_inner, dt),
        "wbc": L.dense_init(ks[2], d, 2 * N, dt),
        "wdt": L.dense_init(ks[3], d, H, dt),
        "conv_x_w": (jax.random.normal(ks[4], (cfg.ssm.d_conv, d_inner))
                     * (1.0 / np.sqrt(cfg.ssm.d_conv))).astype(dt),
        "conv_x_b": jnp.zeros((d_inner,), dt),
        "conv_bc_w": (jax.random.normal(ks[5], (cfg.ssm.d_conv, 2 * N))
                      * (1.0 / np.sqrt(cfg.ssm.d_conv))).astype(dt),
        "conv_bc_b": jnp.zeros((2 * N,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), np.log(np.expm1(0.01)), jnp.float32),
        "norm": L.init_norm(d_inner, "rmsnorm", dt),
        "out_proj": L.dense_init(ks[6], d_inner, d, dt,
                                 1.0 / np.sqrt(2 * cfg.n_layers)),
    }


def _m2_project(p, x, cfg):
    d_inner, H, N, P = _m2_dims(cfg)
    z = x @ p["wz"]
    xin = x @ p["wx"]
    bc = x @ p["wbc"]
    dt_ = x @ p["wdt"]
    return z, xin, bc[..., :N], bc[..., N:], dt_


def _conv_window(x: jnp.ndarray, n: int, lengths=None) -> jnp.ndarray:
    """The ``n`` rows of ``x`` (B,S,d) that end at each row's last real
    position (``lengths - 1``, or ``S - 1``), zeros before position 0."""
    B, S = x.shape[:2]
    if lengths is None:
        return x[:, -n:] if S >= n else jnp.pad(
            x, ((0, 0), (n - S, 0), (0, 0)))
    idx = lengths[:, None] - n + jnp.arange(n)[None]              # (B, n)
    rows = jnp.take_along_axis(x, jnp.clip(idx, 0, S - 1)[..., None],
                               axis=1)
    return jnp.where((idx >= 0)[..., None], rows, 0)


def mamba2_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                   par=None, lengths=None) -> Tuple[jnp.ndarray, MixerState]:
    """Full-sequence Mamba-2 mixer.  With ``lengths`` (B,), each row's
    positions from its length on are padding: ``dt`` is 0 there (decay 1,
    no input), so the final state and the conv windows are those of the
    last real token."""
    B, S, d = x.shape
    d_inner, H, N, P = _m2_dims(cfg)
    z, xin, Bv, Cv, dt_ = _m2_project(p, x, cfg)
    xin = jax.nn.silu(causal_conv(xin, p["conv_x_w"], p["conv_x_b"]))
    bc = jax.nn.silu(causal_conv(jnp.concatenate([Bv, Cv], -1),
                                 p["conv_bc_w"], p["conv_bc_b"]))
    Bv, Cv = bc[..., :N], bc[..., N:]

    dt_f = jax.nn.softplus(dt_.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    if lengths is not None:
        dt_f = jnp.where(L.valid_mask(lengths, S)[..., None], dt_f, 0.0)
    a = -jnp.exp(p["A_log"])                                        # (H,)
    log_decay = (dt_f * a).transpose(0, 2, 1)                       # (B,H,S)

    # map to the generalized op: k=Bv (dk=N), v=dt*x (dv=P), q=Cv
    k = jnp.broadcast_to(Bv[:, :, None, :], (B, S, H, N)).transpose(0, 2, 1, 3)
    q = jnp.broadcast_to(Cv[:, :, None, :], (B, S, H, N)).transpose(0, 2, 1, 3)
    xh = xin.reshape(B, S, H, P)
    v = (xh * dt_f[..., None].astype(xh.dtype)).transpose(0, 2, 1, 3)  # (B,H,S,P)
    k, q, v = shard_heads(k, par), shard_heads(q, par), shard_heads(v, par)
    log_decay = shard_heads(log_decay, par)

    y, S_fin = chunked_la_scalar(q, k, v, log_decay, cfg.ssm.chunk,
                                 unroll=cfg.cost_probe)
    y = y + p["D"][None, :, None, None] * xh.transpose(0, 2, 1, 3)
    y = y.transpose(0, 2, 1, 3).reshape(B, S, d_inner).astype(x.dtype)
    y = L.rmsnorm_gated(y, p["norm"]["scale"], z, cfg.norm_eps)
    out = y @ p["out_proj"]

    # NOTE: conv caches hold pre-activation inputs of the last d_conv-1 steps
    z2, xin2, Bv2, Cv2, _ = _m2_project(
        p, _conv_window(x, cfg.ssm.d_conv - 1, lengths), cfg)
    state = {"S": _store_state(S_fin, cfg),
             "conv_x": xin2,
             "conv_bc": jnp.concatenate([Bv2, Cv2], -1)}
    return out, state


def mamba2_init_state(B: int, cfg: ModelConfig) -> MixerState:
    d_inner, H, N, P = _m2_dims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    return {"S": OPS.init_state(B, H, N, P, cfg.state_quant),
            "conv_x": jnp.zeros((B, cfg.ssm.d_conv - 1, d_inner), dt),
            "conv_bc": jnp.zeros((B, cfg.ssm.d_conv - 1, 2 * N), dt)}


def mamba2_decode(p: Params, x: jnp.ndarray, state: MixerState,
                  cfg: ModelConfig, seed) -> Tuple[jnp.ndarray, MixerState]:
    """x: (B, 1, d) one token."""
    B = x.shape[0]
    d_inner, H, N, P = _m2_dims(cfg)
    z, xin, Bv, Cv, dt_ = _m2_project(p, x[:, 0], cfg)
    xin, conv_x_state = causal_conv_step(xin, state["conv_x"],
                                         p["conv_x_w"], p["conv_x_b"])
    xin = jax.nn.silu(xin)
    bc, conv_bc_state = causal_conv_step(jnp.concatenate([Bv, Cv], -1),
                                         state["conv_bc"],
                                         p["conv_bc_w"], p["conv_bc_b"])
    bc = jax.nn.silu(bc)
    Bv, Cv = bc[..., :N], bc[..., N:]

    dt_f = jax.nn.softplus(dt_.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["A_log"])
    decay = _DECAY_HOOKS["mamba2"]((dt_f * a)[..., None])           # (B,H,1)

    k = jnp.broadcast_to(Bv[:, None, :], (B, H, N))
    q = jnp.broadcast_to(Cv[:, None, :], (B, H, N))
    xh = xin.reshape(B, H, P)
    v = xh * dt_f[..., None]

    Sn, y = _spu_state_update(state["S"], decay, k, v, q, cfg, seed)  # y (B,H,P)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(B, d_inner).astype(x.dtype)
    y = L.rmsnorm_gated(y, p["norm"]["scale"], z, cfg.norm_eps)
    out = (y @ p["out_proj"])[:, None]
    return out, {"S": Sn, "conv_x": conv_x_state, "conv_bc": conv_bc_state}


# ---------------------------------------------------------------------------
# GLA-family (GLA / RetNet / HGRN2) shared projections
# ---------------------------------------------------------------------------

def _gla_dims(cfg: ModelConfig):
    sc = cfg.ssm
    H = sc.n_heads or cfg.n_heads
    dk = sc.dk_head or cfg.head_dim
    dv = sc.dv_head or cfg.head_dim
    return H, dk, dv


def init_gla_family(key, cfg: ModelConfig, kind: str) -> Params:
    d = cfg.d_model
    H, dk, dv = _gla_dims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p = {
        "wq": L.dense_init(ks[0], d, H * dk, dt),
        "wk": L.dense_init(ks[1], d, H * dk, dt),
        "wv": L.dense_init(ks[2], d, H * dv, dt),
        "wg_out": L.dense_init(ks[3], d, H * dv, dt),
        "wo": L.dense_init(ks[4], H * dv, d, dt,
                           1.0 / np.sqrt(2 * cfg.n_layers)),
    }
    if kind == "gla":
        p["wga"] = L.dense_init(ks[5], d, 16, dt)
        p["wgb"] = L.dense_init(ks[6], 16, H * dk, dt)
        p["gb"] = jnp.full((H * dk,), 4.0, jnp.float32)   # bias gates toward 1
    elif kind == "hgrn2":
        p["wf"] = L.dense_init(ks[5], d, H * dk, dt)
        p["fb"] = jnp.zeros((H * dk,), jnp.float32)
        # depth-dependent forget lower bound (set by the model assembler)
        p["beta"] = jnp.zeros((1,), jnp.float32)
    elif kind == "retnet":
        pass  # fixed per-head decay, no gate params
    else:
        raise ValueError(kind)
    return p


def _retnet_log_gamma(H: int) -> jnp.ndarray:
    return jnp.log1p(-jnp.exp2(-5.0 - jnp.arange(H, dtype=jnp.float32)))


def _gla_family_qkv(p, x, cfg, kind):
    B, S, d = x.shape
    H, dk, dv = _gla_dims(cfg)
    q = (x @ p["wq"]).reshape(B, S, H, dk).transpose(0, 2, 1, 3)
    k = (x @ p["wk"]).reshape(B, S, H, dk).transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(B, S, H, dv).transpose(0, 2, 1, 3)
    if kind == "gla":
        g = (x @ p["wga"]) @ p["wgb"] + p["gb"]
        log_f = jax.nn.log_sigmoid(g.astype(jnp.float32)) / 16.0
        log_f = jnp.maximum(log_f, cfg.ssm.log_decay_min)
        log_f = log_f.reshape(B, S, H, dk).transpose(0, 2, 1, 3)
        q = q * (dk ** -0.5)
    elif kind == "hgrn2":
        f_pre = (x @ p["wf"]) + p["fb"]
        beta = p["beta"][0]
        fgate = beta + (1.0 - beta) * jax.nn.sigmoid(f_pre.astype(jnp.float32))
        log_f = jnp.maximum(jnp.log(fgate + 1e-9), cfg.ssm.log_decay_min)
        log_f = log_f.reshape(B, S, H, dk).transpose(0, 2, 1, 3)
        # HGRN2: k = 1 - f  (input gate complementary to forget gate)
        k = (1.0 - jnp.exp(log_f)).astype(k.dtype)
        q = q * (dk ** -0.5)
    else:  # retnet: scalar per-head decay
        log_f = jnp.broadcast_to(_retnet_log_gamma(H)[None, :, None], (B, H, S))
        q = q * (dk ** -0.5)
    return q, k, v, log_f


def gla_family_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                       kind: str, par=None) -> Tuple[jnp.ndarray, MixerState]:
    B, S, d = x.shape
    H, dk, dv = _gla_dims(cfg)
    q, k, v, log_f = _gla_family_qkv(p, x, cfg, kind)
    q, k, v = shard_heads(q, par), shard_heads(k, par), shard_heads(v, par)
    log_f = shard_heads(log_f, par)
    if kind == "retnet":
        y, S_fin = chunked_la_scalar(q, k, v, log_f, cfg.ssm.chunk,
                                     unroll=cfg.cost_probe)
    else:
        y, S_fin = chunked_la_vector(q, k, v, log_f, cfg.ssm.chunk,
                                     unroll=cfg.cost_probe)
    y = L.head_rmsnorm(y, cfg.norm_eps)                    # (B,H,S,dv)
    y = y.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
    gate = jax.nn.silu(x @ p["wg_out"])
    out = (y.astype(x.dtype) * gate) @ p["wo"]
    return out, {"S": _store_state(S_fin, cfg)}


def gla_family_init_state(B: int, cfg: ModelConfig) -> MixerState:
    H, dk, dv = _gla_dims(cfg)
    return {"S": OPS.init_state(B, H, dk, dv, cfg.state_quant)}


def gla_family_decode(p: Params, x: jnp.ndarray, state: MixerState,
                      cfg: ModelConfig, kind: str, seed
                      ) -> Tuple[jnp.ndarray, MixerState]:
    B = x.shape[0]
    H, dk, dv = _gla_dims(cfg)
    q, k, v, log_f = _gla_family_qkv(p, x, cfg, kind)      # (B,H,1,*)
    q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]
    decay = _DECAY_HOOKS[kind](log_f)
    Sn, y = _spu_state_update(state["S"], decay, k, v, q, cfg, seed)
    y = L.head_rmsnorm(y, cfg.norm_eps).reshape(B, 1, H * dv)
    gate = jax.nn.silu(x @ p["wg_out"])
    out = (y.astype(x.dtype) * gate) @ p["wo"]
    return out, {"S": Sn}


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix-memory block)
# ---------------------------------------------------------------------------

def _mlstm_dims(cfg: ModelConfig):
    sc = cfg.ssm
    d_up = sc.expand * cfg.d_model
    H = sc.n_heads or cfg.n_heads
    dk = d_up // H
    dv = d_up // H
    dv_aug = dv + 16            # [v, 1, 0...] -- normalizer folded in
    return d_up, H, dk, dv, dv_aug


def init_mlstm(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    d_up, H, dk, dv, _ = _mlstm_dims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    k_extra = jax.random.split(ks[0])
    return {
        "wu": L.dense_init(k_extra[0], d, d_up, dt),
        "wz": L.dense_init(k_extra[1], d, d_up, dt),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm.d_conv, d_up))
                   * (1.0 / np.sqrt(cfg.ssm.d_conv))).astype(dt),
        "conv_b": jnp.zeros((d_up,), dt),
        # block-diagonal per-head projections (xLSTM parameterization):
        # (H, dk, dk) instead of dense (d_up, d_up) -- H x fewer params
        "wq": (jax.random.normal(ks[2], (H, dk, dk)) / np.sqrt(dk)).astype(dt),
        "wk": (jax.random.normal(ks[3], (H, dk, dk)) / np.sqrt(dk)).astype(dt),
        "wv": (jax.random.normal(ks[4], (H, dv, dv)) / np.sqrt(dv)).astype(dt),
        "wi": L.dense_init(ks[5], d_up, H, jnp.float32),
        "wf": L.dense_init(ks[6], d_up, H, jnp.float32),
        "fb": jnp.full((H,), 3.0, jnp.float32),   # bias forget gates open
        "hnorm": jnp.ones((H, dv), dt),
        "down": L.dense_init(ks[7], d_up, d, dt,
                             1.0 / np.sqrt(2 * cfg.n_layers)),
    }


def _mlstm_gates_qkv(p, u, uc, cfg):
    B, S, d_up = u.shape
    _, H, dk, dv, dv_aug = _mlstm_dims(cfg)
    uh = uc.reshape(B, S, H, dk)
    q = jnp.einsum("bshd,hde->bhse", uh, p["wq"])
    k = jnp.einsum("bshd,hde->bhse", uh, p["wk"]) * dk ** -0.5
    v = jnp.einsum("bshd,hde->bhse", u.reshape(B, S, H, dv), p["wv"])
    i_log = jnp.clip((u @ p["wi"]).astype(jnp.float32), -12.0, 4.0)
    log_f = jax.nn.log_sigmoid((u @ p["wf"]).astype(jnp.float32) + p["fb"])
    i_log = i_log.transpose(0, 2, 1)              # (B,H,S)
    log_f = log_f.transpose(0, 2, 1)
    # fold the exp input gate into k; augment v with a ones column so the
    # normalizer n is carried as extra state rows (padded to MX group size)
    k_eff = (k.astype(jnp.float32) * jnp.exp(i_log)[..., None]).astype(k.dtype)
    ones = jnp.ones(v.shape[:-1] + (1,), v.dtype)
    zeros = jnp.zeros(v.shape[:-1] + (dv_aug - dv - 1,), v.dtype)
    v_aug = jnp.concatenate([v, ones, zeros], axis=-1)
    return q, k_eff, v_aug, log_f


def mlstm_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                  par=None) -> Tuple[jnp.ndarray, MixerState]:
    B, S, d = x.shape
    d_up, H, dk, dv, dv_aug = _mlstm_dims(cfg)
    u, z = x @ p["wu"], x @ p["wz"]
    uc = jax.nn.silu(causal_conv(u, p["conv_w"], p["conv_b"]))
    q, k_eff, v_aug, log_f = _mlstm_gates_qkv(p, u, uc, cfg)
    q, k_eff, v_aug = (shard_heads(q, par), shard_heads(k_eff, par),
                       shard_heads(v_aug, par))
    y_aug, S_fin = chunked_la_scalar(q, k_eff, v_aug, log_f, cfg.ssm.chunk,
                                     unroll=cfg.cost_probe)
    y, n_dot = y_aug[..., :dv], y_aug[..., dv]
    h = y / jnp.maximum(jnp.abs(n_dot), 1.0)[..., None]
    h = L.head_rmsnorm(h, cfg.norm_eps) * p["hnorm"][None, :, None, :]
    h = h.transpose(0, 2, 1, 3).reshape(B, S, d_up).astype(x.dtype)
    out = (h * jax.nn.silu(z)) @ p["down"]
    state = {"S": _store_state(S_fin, cfg),
             "conv": u[:, -(cfg.ssm.d_conv - 1):, :]}
    return out, state


def mlstm_init_state(B: int, cfg: ModelConfig) -> MixerState:
    d_up, H, dk, dv, dv_aug = _mlstm_dims(cfg)
    return {"S": OPS.init_state(B, H, dk, dv_aug, cfg.state_quant),
            "conv": jnp.zeros((B, cfg.ssm.d_conv - 1, d_up),
                              jnp.dtype(cfg.param_dtype))}


def mlstm_decode(p: Params, x: jnp.ndarray, state: MixerState,
                 cfg: ModelConfig, seed) -> Tuple[jnp.ndarray, MixerState]:
    B = x.shape[0]
    d_up, H, dk, dv, dv_aug = _mlstm_dims(cfg)
    u, z = x[:, 0] @ p["wu"], x[:, 0] @ p["wz"]
    conv_out, conv_state = causal_conv_step(u, state["conv"],
                                            p["conv_w"], p["conv_b"])
    uc = jax.nn.silu(conv_out)
    q, k_eff, v_aug, log_f = _mlstm_gates_qkv(
        p, u[:, None], uc[:, None], cfg)
    q, k_eff, v_aug = q[:, :, 0], k_eff[:, :, 0], v_aug[:, :, 0]
    decay = _DECAY_HOOKS["mlstm"](log_f)                    # (B,H,1)
    Sn, y_aug = _spu_state_update(state["S"], decay, k_eff, v_aug, q,
                                  cfg, seed)
    y, n_dot = y_aug[..., :dv], y_aug[..., dv]
    h = y / jnp.maximum(jnp.abs(n_dot), 1.0)[..., None]
    h = L.head_rmsnorm(h, cfg.norm_eps) * p["hnorm"][None]
    h = h.reshape(B, d_up).astype(x.dtype)
    out = ((h * jax.nn.silu(z)) @ p["down"])[:, None]
    return out, {"S": Sn, "conv": conv_state}


# ---------------------------------------------------------------------------
# sLSTM (vector recurrence; inherently sequential)
# ---------------------------------------------------------------------------

def _slstm_dims(cfg: ModelConfig):
    H = cfg.ssm.n_heads or cfg.n_heads
    dh = cfg.d_model // H
    return H, dh


def init_slstm(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    H, dh = _slstm_dims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    return {
        "wx": L.dense_init(ks[0], d, 4 * d, dt),            # z,i,f,o
        "r": (jax.random.normal(ks[1], (H, dh, 4 * dh))
              / np.sqrt(dh)).astype(dt),
        "b": jnp.zeros((4 * d,), jnp.float32),
        "out": L.dense_init(ks[2], d, d, dt,
                            1.0 / np.sqrt(2 * cfg.n_layers)),
    }


def _slstm_cell(p, gx, carry, cfg):
    """gx: (B,H,4*dh) pre-activations from x; carry: (c,n,m,h)."""
    c_prev, n_prev, m_prev, h_prev = carry
    rec = jnp.einsum("bhd,hde->bhe", h_prev.astype(gx.dtype), p["r"])
    g = (gx + rec).astype(jnp.float32)
    zt, it, ft, ot = jnp.split(g, 4, axis=-1)
    zt = jnp.tanh(zt)
    log_f = jax.nn.log_sigmoid(ft)
    m_t = jnp.maximum(log_f + m_prev, it)
    i_p = jnp.exp(it - m_t)
    f_p = jnp.exp(log_f + m_prev - m_t)
    c_t = f_p * c_prev + i_p * zt
    n_t = f_p * n_prev + i_p
    h_t = jax.nn.sigmoid(ot) * c_t / jnp.maximum(n_t, 1e-6)
    return (c_t, n_t, m_t, h_t)


def slstm_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                  par=None) -> Tuple[jnp.ndarray, MixerState]:
    B, S, d = x.shape
    H, dh = _slstm_dims(cfg)
    gx = ((x @ p["wx"]) + p["b"].astype(x.dtype)).reshape(B, S, H, 4 * dh)

    def run(r_param, gx_local):
        """Per-shard sequential scan over time (batch-split)."""
        gxt = jnp.moveaxis(gx_local, 1, 0)          # (S, B_l, H, 4dh)
        b_l = gx_local.shape[0]
        z0 = jnp.zeros((b_l, H, dh), jnp.float32)
        carry0 = (z0, z0, jnp.full_like(z0, -1e30), z0)

        def body(carry, g):
            new = _slstm_cell({"r": r_param}, g, carry, cfg)
            return new, new[3]

        carry, hs = jax.lax.scan(body, carry0, gxt)
        return jnp.moveaxis(hs, 0, 1), carry        # (B_l, S, H, dh), states

    # The 4096-step recurrence must not be re-partitioned per step: under
    # GSPMD the backward's per-step dynamic slices churn the partitioner
    # into involuntary full rematerializations.  shard_map makes the
    # sharding manual (batch split, everything else replicated) so the loop
    # body is compiled exactly once.
    if par is not None and hasattr(par, "mesh") \
            and B % par.batch_size_divisor == 0:
        from jax.sharding import PartitionSpec as P
        bt = P(par.batch_axes)
        hs, carry = jax.shard_map(
            run, mesh=par.mesh,
            in_specs=(P(), P(par.batch_axes, None, None, None)),
            out_specs=(P(par.batch_axes, None, None, None),
                       (bt, bt, bt, bt)),
            check_vma=False,
        )(p["r"], gx)
    else:
        hs, carry = run(p["r"], gx)
    h = hs.reshape(B, S, d).astype(x.dtype)
    out = h @ p["out"]
    state = {"c": carry[0], "n": carry[1], "m": carry[2], "h": carry[3]}
    return out, state


def slstm_init_state(B: int, cfg: ModelConfig) -> MixerState:
    H, dh = _slstm_dims(cfg)
    z0 = jnp.zeros((B, H, dh), jnp.float32)
    return {"c": z0, "n": z0, "m": jnp.full_like(z0, -1e30), "h": z0}


def slstm_decode(p: Params, x: jnp.ndarray, state: MixerState,
                 cfg: ModelConfig, seed) -> Tuple[jnp.ndarray, MixerState]:
    B = x.shape[0]
    H, dh = _slstm_dims(cfg)
    gx = ((x[:, 0] @ p["wx"]) + p["b"].astype(x.dtype)).reshape(B, H, 4 * dh)
    carry = (state["c"], state["n"], state["m"], state["h"])
    c, n, m, h = _slstm_cell(p, gx, carry, cfg)
    out = (h.reshape(B, cfg.d_model).astype(x.dtype) @ p["out"])[:, None]
    return out, {"c": c, "n": n, "m": m, "h": h}
