"""Core NN layers (functional, pytree params) shared by all architectures."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig, MoEConfig

Params = dict


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0) -> jnp.ndarray:
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# padding
# ---------------------------------------------------------------------------

def valid_mask(lengths: jnp.ndarray, S: int) -> jnp.ndarray:
    """(B, S) True at each row's first ``lengths[b]`` positions."""
    return jnp.arange(S)[None] < lengths[:, None]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(d: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x: jnp.ndarray, kind: str, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def rmsnorm_gated(x: jnp.ndarray, scale: jnp.ndarray, gate: jnp.ndarray,
                  eps: float = 1e-5) -> jnp.ndarray:
    """Mamba-2 style RMSNorm(x * silu(gate))."""
    xf = (x * jax.nn.silu(gate.astype(jnp.float32))).astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def head_rmsnorm(x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Per-head RMSNorm without scale (GLA/RetNet output norm)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype)


# ---------------------------------------------------------------------------
# positional embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, dh) or (..., S, dh); positions: (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                              # (dh/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs     # (..., S, dh/2)
    if x.ndim == ang.ndim + 1:                                 # head axis present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sincos_pos_emb(S: int, d: int, dtype) -> jnp.ndarray:
    pos = np.arange(S)[:, None]
    div = np.exp(np.arange(0, d, 2) * (-np.log(10000.0) / d))
    pe = np.zeros((S, d), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(pe, dtype)


# ---------------------------------------------------------------------------
# feed-forward variants
# ---------------------------------------------------------------------------

def init_ffn(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, dff = cfg.d_model, (d_ff or cfg.d_ff)
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    scale_out = 1.0 / np.sqrt(2 * cfg.n_layers)
    if cfg.ffn_kind_inner in ("swiglu", "geglu"):
        return {"wi": dense_init(k1, d, dff, dt),
                "wg": dense_init(k2, d, dff, dt),
                "wo": dense_init(k3, dff, d, dt, scale_out)}
    return {"wi": dense_init(k1, d, dff, dt),
            "wo": dense_init(k3, dff, d, dt, scale_out)}


def apply_ffn(p: Params, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["wi"]) * (x @ p["wg"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ p["wi"]) * (x @ p["wg"])
    elif kind == "gelu":
        h = jax.nn.gelu(x @ p["wi"])
    elif kind == "relu":
        h = jax.nn.relu(x @ p["wi"])
    else:
        raise ValueError(kind)
    return h @ p["wo"]


def shared_mlp(p: Params, adapter: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Zamba2's shared-block MLP: a fused gate/up projection plus the
    application's own low-rank adapter, exact (erf) GELU on the gate half
    times the up half, then the down projection."""
    gu = x @ p["wi"] + (x @ adapter["lora_a"]) @ adapter["lora_b"]
    gate, up = jnp.split(gu, 2, axis=-1)
    return (jax.nn.gelu(gate, approximate=False) * up) @ p["wo"]


# ---------------------------------------------------------------------------
# Mixture-of-Experts (expert-parallel over the 'model' mesh axis)
# ---------------------------------------------------------------------------
#
# Token routing uses the destination->source indirection trick: a cheap int32
# scatter builds, for every (expert, slot), the index of the token assigned
# there; the expensive (E_local, Cap, d) buffer is then a single gather and
# the FFN runs as grouped einsums.  Tokens beyond expert capacity are
# dropped (standard capacity-factor semantics).
#
# Under expert parallelism, tokens are replicated across the 'model' axis
# (the activation layout GSPMD already uses for TP), each shard computes its
# local experts only, and one psum over 'model' combines -- the same
# collective cost as the TP FFN it replaces.

def init_moe(key, cfg: ModelConfig) -> Params:
    mc = cfg.moe
    d, de = cfg.d_model, mc.d_expert
    dt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 5)
    scale_out = 1.0 / np.sqrt(2 * cfg.n_layers)
    p = {
        "router": dense_init(keys[0], d, mc.n_experts, jnp.float32),
        "wi": _stack_init(keys[1], mc.n_experts, d, de, dt),
        "wg": _stack_init(keys[2], mc.n_experts, d, de, dt),
        "wo": _stack_init(keys[3], mc.n_experts, de, d, dt, scale_out),
    }
    if mc.n_shared:
        p["shared"] = init_ffn(keys[4], cfg, d_ff=mc.d_expert * mc.n_shared)
    return p


def _stack_init(key, n: int, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(key, (n, d_in, d_out)) * std).astype(dtype)


def _moe_dispatch_compute(x_flat: jnp.ndarray, sel: jnp.ndarray, w: jnp.ndarray,
                          wi, wg, wo, e_offset, n_local: int, cap: int,
                          kind: str) -> jnp.ndarray:
    """Compute the local experts' contribution for all tokens.

    x_flat (N, d); sel (N, k) global expert ids; w (N, k) combine weights;
    wi/wg/wo (E_local, ...); e_offset: first global id owned locally.
    """
    N, d = x_flat.shape
    k = sel.shape[-1]
    entry_tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)           # (N*k,)
    sel_f = sel.reshape(-1).astype(jnp.int32)
    w_f = w.reshape(-1)
    local_e = sel_f - e_offset
    is_local = (local_e >= 0) & (local_e < n_local)
    # slot within expert: rank among local entries of the same expert
    oh = jax.nn.one_hot(jnp.where(is_local, local_e, n_local), n_local + 1,
                        dtype=jnp.int32)                                 # (N*k, E_l+1)
    slot = (jnp.cumsum(oh, axis=0) - oh)                                  # exclusive
    slot = jnp.take_along_axis(slot, jnp.where(is_local, local_e, n_local)[:, None],
                               axis=1)[:, 0]
    keep = is_local & (slot < cap)
    e_idx = jnp.where(keep, local_e, n_local)                            # OOB drops
    s_idx = jnp.where(keep, slot, cap)

    # destination -> source token index
    src = jnp.full((n_local + 1, cap + 1), N, jnp.int32)
    src = src.at[e_idx, s_idx].set(entry_tok, mode="drop")
    src = src[:n_local, :cap]                                            # (E_l, Cap)
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)], axis=0)
    buf = x_pad[src]                                                     # (E_l,Cap,d)

    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", buf, wi)) * jnp.einsum(
            "ecd,edf->ecf", buf, wg)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, wi))
    y_e = jnp.einsum("ecf,efd->ecd", h, wo)                              # (E_l,Cap,d)

    # combine weights per (expert, slot)
    wbuf = jnp.zeros((n_local + 1, cap + 1), w_f.dtype)
    wbuf = wbuf.at[e_idx, s_idx].set(w_f, mode="drop")[:n_local, :cap]
    y_e = y_e * wbuf[..., None].astype(y_e.dtype)

    out = jnp.zeros((N + 1, d), y_e.dtype)
    out = out.at[src.reshape(-1)].add(y_e.reshape(-1, d), mode="drop")
    return out[:N]


def _moe_local(x: jnp.ndarray, router, wi, wg, wo, cfg: ModelConfig,
               ep_axis: Optional[str]) -> jnp.ndarray:
    """Route + dispatch + expert FFNs for the tokens on this shard.

    With ep_axis set, runs inside shard_map: this shard holds E/tp experts
    and the local batch slice; routing decisions are computed locally (the
    router is replicated) and one psum over ep_axis combines expert outputs.
    Token movement is zero -- each (data, model) shard pair computes exactly
    the (local tokens x local experts) block.
    """
    mc = cfg.moe
    B, S, d = x.shape
    x_flat = x.reshape(-1, d)
    logits = (x_flat.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, sel = jax.lax.top_k(probs, mc.top_k)                        # (N, k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)

    n_tokens = x_flat.shape[0]
    cap = int(np.ceil(n_tokens * mc.top_k / mc.n_experts * mc.capacity_factor))
    cap = max(cap, 4)

    if ep_axis is None:
        out = _moe_dispatch_compute(x_flat, sel, w, wi, wg, wo,
                                    e_offset=0, n_local=mc.n_experts,
                                    cap=cap, kind=cfg.ffn_kind_inner)
    else:
        n_shards = jax.lax.axis_size(ep_axis)
        n_local = mc.n_experts // n_shards
        e_offset = jax.lax.axis_index(ep_axis) * n_local
        out = _moe_dispatch_compute(x_flat, sel, w, wi, wg, wo,
                                    e_offset=e_offset, n_local=n_local,
                                    cap=cap, kind=cfg.ffn_kind_inner)
        out = jax.lax.psum(out, ep_axis)
    return out.reshape(B, S, d).astype(x.dtype)


def apply_moe(p: Params, x: jnp.ndarray, cfg: ModelConfig,
              par=None) -> jnp.ndarray:
    """MoE FFN.  x: (B, S, d).  par: repro.dist.sharding.Parallel or None."""
    from jax.sharding import PartitionSpec as P  # local import, no cycle
    mc = cfg.moe
    use_ep = (par is not None and par.tp > 1
              and mc.n_experts % par.tp == 0)
    if use_ep:
        model = par.model_axis
        bspec = P(par.batch_axes, None, None)
        espec = P(model, None, None)
        out = jax.shard_map(
            functools.partial(_moe_local, cfg=cfg, ep_axis=model),
            mesh=par.mesh,
            in_specs=(bspec, P(None, None), espec, espec, espec),
            out_specs=bspec,
            check_vma=False,
        )(x, p["router"], p["wi"], p["wg"], p["wo"])
    else:
        out = _moe_local(x, p["router"], p["wi"], p["wg"], p["wo"], cfg, None)
    if mc.n_shared:
        out = out + apply_ffn(p["shared"], x, cfg.ffn_kind_inner)
    return out


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def chunked_softmax_xent(x: jnp.ndarray, lm_head: jnp.ndarray,
                         labels: jnp.ndarray, mask: jnp.ndarray,
                         chunk: int = 1024, unroll: bool = False) -> jnp.ndarray:
    """Cross-entropy over huge vocabularies without a (B,S,V) logits buffer.

    x: (B, S, d) final hidden states; lm_head: (d, V); labels/mask: (B, S).
    Scans over sequence chunks; each chunk's logits are (B, chunk, V) and die
    immediately.  Essential for paligemma's 257k vocab.
    """
    B, S, d = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk
    xc = x.reshape(B, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)
    mc = mask.reshape(B, nc, chunk).swapaxes(0, 1)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, inp):
        # checkpointed: the backward recomputes the chunk logits instead of
        # saving a (B, chunk, V) residual per chunk
        tot, cnt = carry
        xb, lb, mb = inp
        logits = (xb @ lm_head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via masked reduction, not take_along_axis: a gather
        # across the model-sharded vocab dim would force an all-gather of the
        # logits chunk under GSPMD; the masked sum reduces locally and
        # all-reduces a (B, chunk) scalar field instead.
        vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        gold = jnp.sum(jnp.where(vocab_ids == lb[..., None], logits, 0.0),
                       axis=-1)
        nll = (logz - gold) * mb
        return (tot + nll.sum(), cnt + mb.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                 (xc, lc, mc), unroll=unroll)
    return tot / jnp.maximum(cnt, 1.0)
