"""Attention mixers: GQA (llama-family) and MLA (DeepSeek-V2).

Training/prefill use a memory-efficient blockwise ("flash") formulation in
pure JAX -- the paper runs prefill on the GPU in compute-intensive form, and
on TPU the MXU-friendly einsum form is the analogue.  Decode uses the
MX8-quantized KV cache through the registered SPU ops (``kv_append`` +
``attn_decode``/``mla_decode``, repro/ops/attention.py) in one unified step.

MLA runs in *absorbed* form everywhere: queries are projected into the
compressed-latent space so the cache is a single (kv_lora + rope) stream --
this is what makes the MLA decode cache 576 bytes/token instead of
2 * H * dh, and it maps directly onto the kernel's MLA mode.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops as OPS
from repro.core import attention_cache as AC
from repro.models import layers as L
from repro.models.config import ModelConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# blockwise causal attention (pure JAX flash-style, memory-efficient VJP)
# ---------------------------------------------------------------------------
#
# The backward pass recomputes score chunks instead of saving them (the
# flash-attention trick); without this, differentiating the nested scans
# saves every (q_chunk x kv_chunk) probability block and the training-step
# memory explodes ~8x (measured in EXPERIMENTS.md §Perf iteration 1).

def _mask_chunk(s, q_idx, k_idx, q_chunk, kv_chunk, prefix_len):
    """Additive mask, (qc, kc) only.

    Deliberately NOT a broadcast boolean `where`: the where-VJP would save
    the mask at the broadcast (B,KVH,G,qc,kc) shape, and being
    input-independent it gets hoisted out of the layer scan and stacked over
    every (q,kv) chunk pair -- a multi-GiB pred buffer (measured; see
    EXPERIMENTS.md §Perf).  An additive f32 (qc,kc) mask has an identity VJP
    and costs 4 bytes per chunk-pair cell."""
    qp = q_idx * q_chunk + jnp.arange(q_chunk)
    kp = k_idx * kv_chunk + jnp.arange(kv_chunk)
    ok = qp[:, None] >= kp[None, :]
    if prefix_len:
        ok = ok | (kp[None, :] < prefix_len)
    return s + jnp.where(ok, 0.0, NEG_INF).astype(s.dtype)


def _flash_fwd_impl(qb, kb, vb, causal, prefix_len, q_chunk, kv_chunk,
                    unroll=False):
    """qb: (nq,B,KVH,G,qc,dh) pre-scaled f32; kb/vb: (nk,B,KVH,kc,d*).

    Returns (out (nq,B,KVH,G,qc,dv), lse (nq,B,KVH,G,qc,1))."""
    nq, B, KVH, G, qc, dh = qb.shape
    nk = kb.shape[0]
    dv = vb.shape[-1]

    def q_body(_, qi_inp):
        qi, q_idx = qi_inp

        def kv_body(carry, kv_inp):
            m, l, acc = carry
            kj, vj, k_idx = kv_inp
            s = jnp.einsum("bngqd,bnkd->bngqk", qi, kj)
            if causal:
                s = _mask_chunk(s, q_idx, k_idx, q_chunk, kv_chunk, prefix_len)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bngqk,bnkv->bngqv", p, vj)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, qc, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros_like(m0)
        a0 = jnp.zeros((B, KVH, G, qc, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (kb, vb, jnp.arange(nk)), unroll=unroll)
        l = jnp.maximum(l, 1e-30)
        return None, (acc / l, m + jnp.log(l))

    _, (out, lse) = jax.lax.scan(q_body, None, (qb, jnp.arange(nq)),
                                 unroll=unroll)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qb, kb, vb, causal, prefix_len, q_chunk, kv_chunk, unroll=False):
    out, _ = _flash_fwd_impl(qb, kb, vb, causal, prefix_len, q_chunk, kv_chunk,
                             unroll)
    return out


def _flash_fwd(qb, kb, vb, causal, prefix_len, q_chunk, kv_chunk, unroll=False):
    out, lse = _flash_fwd_impl(qb, kb, vb, causal, prefix_len, q_chunk,
                               kv_chunk, unroll)
    return out, (qb, kb, vb, out, lse)


def _flash_bwd(causal, prefix_len, q_chunk, kv_chunk, unroll, res, dout):
    qb, kb, vb, out, lse = res
    nq, B, KVH, G, qc, dh = qb.shape
    nk = kb.shape[0]
    dv = vb.shape[-1]
    # D_i = rowsum(dO * O)
    Dr = jnp.sum(dout * out, axis=-1, keepdims=True)        # (nq,B,KVH,G,qc,1)

    def q_body(carry, qi_inp):
        dk_acc, dv_acc = carry
        qi, doi, lsei, Di, q_idx = qi_inp

        def kv_body(dq_i, kv_inp):
            kj, vj, k_idx = kv_inp
            s = jnp.einsum("bngqd,bnkd->bngqk", qi, kj)
            if causal:
                s = _mask_chunk(s, q_idx, k_idx, q_chunk, kv_chunk, prefix_len)
            p = jnp.exp(s - lsei)                            # (B,KVH,G,qc,kc)
            dvj = jnp.einsum("bngqk,bngqv->bnkv", p, doi)
            dp = jnp.einsum("bngqv,bnkv->bngqk", doi, vj)
            ds = p * (dp - Di)
            dq_i = dq_i + jnp.einsum("bngqk,bnkd->bngqd", ds, kj)
            dkj = jnp.einsum("bngqk,bngqd->bnkd", ds, qi)
            return dq_i, (dkj, dvj)

        dq0 = jnp.zeros_like(qi)
        dq_i, (dks, dvs) = jax.lax.scan(
            kv_body, dq0, (kb, vb, jnp.arange(nk)), unroll=unroll)
        return (dk_acc + dks, dv_acc + dvs), dq_i

    dk0 = jnp.zeros_like(kb)
    dv0 = jnp.zeros_like(vb)
    (dk, dvb), dq = jax.lax.scan(
        q_body, (dk0, dv0), (qb, dout, lse, Dr, jnp.arange(nq)),
        unroll=unroll)
    return dq, dk, dvb


_flash.defvjp(_flash_fwd, _flash_bwd)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True, prefix_len: int = 0,
                        scale: Optional[float] = None,
                        q_chunk: int = 512, kv_chunk: int = 512,
                        unroll: bool = False) -> jnp.ndarray:
    """q: (B,S,H,dh), k/v: (B,S,KVH,dh|dv) -> (B,S,H,dv).

    Never materializes the (S,S) score matrix, forward or backward; scans q
    chunks (outer) and kv chunks (inner) with running max/sum.  prefix_len >
    0 makes the first prefix_len kv positions visible to every query
    (prefix-LM / VLM).
    """
    B, S, H, dh = q.shape
    KVH = k.shape[2]
    dv = v.shape[-1]
    G = H // KVH
    scale = scale if scale is not None else dh ** -0.5
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    assert S % q_chunk == 0 and S % kv_chunk == 0
    nq, nk = S // q_chunk, S // kv_chunk

    qb = (q.astype(jnp.float32) * scale).reshape(B, nq, q_chunk, KVH, G, dh)
    qb = qb.transpose(1, 0, 3, 4, 2, 5)               # (nq,B,KVH,G,qc,dh)
    kb = k.astype(jnp.float32).reshape(B, nk, kv_chunk, KVH, dh)
    kb = kb.transpose(1, 0, 3, 2, 4)                   # (nk,B,KVH,kc,dh)
    vb = v.astype(jnp.float32).reshape(B, nk, kv_chunk, KVH, dv)
    vb = vb.transpose(1, 0, 3, 2, 4)

    out = _flash(qb, kb, vb, causal, prefix_len, q_chunk, kv_chunk, unroll)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, n_heads: Optional[int] = None,
                   n_kv: Optional[int] = None,
                   d_in: Optional[int] = None) -> L.Params:
    """Projections from a ``d_in``-wide input (``d_model`` by default) back
    to ``d_model``."""
    H = n_heads or cfg.n_heads
    KVH = n_kv or cfg.n_kv_heads
    d, dh = cfg.d_model, cfg.head_dim
    d_in = d_in or d
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d_in, H * dh, dt),
        "wk": L.dense_init(ks[1], d_in, KVH * dh, dt),
        "wv": L.dense_init(ks[2], d_in, KVH * dh, dt),
        "wo": L.dense_init(ks[3], H * dh, d, dt, 1.0 / np.sqrt(2 * cfg.n_layers)),
    }


def attention_forward(p: L.Params, x: jnp.ndarray, cfg: ModelConfig,
                      positions: jnp.ndarray,
                      n_heads: Optional[int] = None,
                      n_kv: Optional[int] = None,
                      prefix_len: int = 0,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Full-sequence attention (train / prefill math); ``scale`` multiplies
    the scores (``head_dim ** -0.5`` by default)."""
    B, S, d = x.shape
    H = n_heads or cfg.n_heads
    KVH = n_kv or cfg.n_kv_heads
    dh = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, dh)
    k = (x @ p["wk"]).reshape(B, S, KVH, dh)
    v = (x @ p["wv"]).reshape(B, S, KVH, dh)
    if cfg.pos_emb == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    o = blockwise_attention(q, k, v, causal=cfg.causal and not cfg.encoder_only,
                            prefix_len=prefix_len, scale=scale,
                            q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                            unroll=cfg.cost_probe)
    return o.reshape(B, S, H * dh) @ p["wo"]


def attention_prefill_kv(p: L.Params, x: jnp.ndarray, cfg: ModelConfig,
                         positions: jnp.ndarray,
                         n_heads: Optional[int] = None,
                         n_kv: Optional[int] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """K/V streams (post-RoPE) for cache construction during prefill."""
    B, S, _ = x.shape
    KVH = n_kv or cfg.n_kv_heads
    dh = cfg.head_dim
    k = (x @ p["wk"]).reshape(B, S, KVH, dh)
    v = (x @ p["wv"]).reshape(B, S, KVH, dh)
    if cfg.pos_emb == "rope":
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return k, v


def attention_decode(p: L.Params, x: jnp.ndarray, cache: AC.KVCache,
                     cfg: ModelConfig, positions: jnp.ndarray, seed,
                     n_heads: Optional[int] = None,
                     n_kv: Optional[int] = None,
                     scale: Optional[float] = None
                     ) -> Tuple[jnp.ndarray, AC.KVCache]:
    """One-token decode: x (B, 1, d) -> (out (B,1,d), updated cache)."""
    B, _, d = x.shape
    H = n_heads or cfg.n_heads
    KVH = n_kv or cfg.n_kv_heads
    dh = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, 1, H, dh)
    k = (x @ p["wk"]).reshape(B, 1, KVH, dh)
    v = (x @ p["wv"]).reshape(B, 1, KVH, dh)
    if cfg.pos_emb == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    # one registered SPU op step: kv_append + attn_decode via the registry
    o, cache = OPS.attention_decode_step(cache, k, v, q.reshape(B, H, dh),
                                         cfg.state_quant, scale=scale,
                                         seed=seed)
    return (o.reshape(B, 1, H * dh).astype(x.dtype) @ p["wo"]), cache


def attention_spec_decode(p: L.Params, x: jnp.ndarray, cache: AC.KVCache,
                          cfg: ModelConfig, positions: jnp.ndarray, seed,
                          n_heads: Optional[int] = None,
                          n_kv: Optional[int] = None,
                          scale: Optional[float] = None
                          ) -> Tuple[jnp.ndarray, AC.KVCache]:
    """Speculative decode: x (B, n, d) -> (out (B, n, d), updated cache).

    Appends all n K/V rows (per-position seeds ``seed + i``), then verifies
    the n queries in one ``spec_verify`` pass -- position j's row is
    bit-identical to the j-th sequential :func:`attention_decode` call.
    """
    B, n, d = x.shape
    H = n_heads or cfg.n_heads
    KVH = n_kv or cfg.n_kv_heads
    dh = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, n, H, dh)
    k = (x @ p["wk"]).reshape(B, n, KVH, dh)
    v = (x @ p["wv"]).reshape(B, n, KVH, dh)
    if cfg.pos_emb == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    o, cache = OPS.attention_spec_step(cache, k, v, q, cfg.state_quant,
                                       scale=scale, seed=seed)
    return (o.reshape(B, n, H * dh).astype(x.dtype) @ p["wo"]), cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2), absorbed form
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig) -> L.Params:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    return {
        "wq_a": L.dense_init(ks[0], d, m.q_lora, dt),
        "q_norm": L.init_norm(m.q_lora, "rmsnorm", dt),
        # per-head query heads: nope part + rope part
        "wq_b": L.dense_init(ks[1], m.q_lora, H * (m.nope_dim + m.rope_dim), dt),
        "wkv_a": L.dense_init(ks[2], d, m.kv_lora + m.rope_dim, dt),
        "kv_norm": L.init_norm(m.kv_lora, "rmsnorm", dt),
        # absorbed projections: W_UK (H, nope, kv_lora), W_UV (H, kv_lora, v)
        "w_uk": (jax.random.normal(ks[3], (H, m.nope_dim, m.kv_lora))
                 / np.sqrt(m.nope_dim)).astype(dt),
        "w_uv": (jax.random.normal(ks[4], (H, m.kv_lora, m.v_dim))
                 / np.sqrt(m.kv_lora)).astype(dt),
        "wo": L.dense_init(ks[5], H * m.v_dim, d, dt,
                           1.0 / np.sqrt(2 * cfg.n_layers)),
    }


def _mla_queries(p, x, cfg, positions):
    """Absorbed queries (B,S,H,kv_lora + rope)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    ql = L.apply_norm(p["q_norm"], x @ p["wq_a"], "rmsnorm", cfg.norm_eps)
    qh = (ql @ p["wq_b"]).reshape(B, S, H, m.nope_dim + m.rope_dim)
    q_nope, q_rope = qh[..., :m.nope_dim], qh[..., m.nope_dim:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    # absorb W_UK: q_eff = q_nope @ W_UK  -> (B,S,H,kv_lora)
    q_eff = jnp.einsum("bshn,hnc->bshc", q_nope, p["w_uk"])
    return jnp.concatenate([q_eff, q_rope], axis=-1)


def _mla_cache_stream(p, x, cfg, positions):
    """Latent cache stream (B,S,kv_lora + rope): values are the first kv_lora."""
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c = L.apply_norm(p["kv_norm"], kv[..., :m.kv_lora], "rmsnorm", cfg.norm_eps)
    k_rope = L.apply_rope(kv[..., m.kv_lora:], positions, cfg.rope_theta)
    return jnp.concatenate([c, k_rope], axis=-1)


def mla_forward(p: L.Params, x: jnp.ndarray, cfg: ModelConfig,
                positions: jnp.ndarray) -> jnp.ndarray:
    """Full-sequence MLA in absorbed form (single latent KV stream)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q = _mla_queries(p, x, cfg, positions)          # (B,S,H,cw)
    ckv = _mla_cache_stream(p, x, cfg, positions)   # (B,S,cw)
    scale = (m.nope_dim + m.rope_dim) ** -0.5
    kv = ckv[:, :, None, :]                          # KVH = 1
    ctx = blockwise_attention(q, kv, kv[..., :m.kv_lora], causal=True,
                              scale=scale, q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk,
                              unroll=cfg.cost_probe)   # (B,S,H,kv_lora)
    o = jnp.einsum("bshc,hcv->bshv", ctx, p["w_uv"])
    return o.reshape(B, S, H * m.v_dim) @ p["wo"]


def mla_decode(p: L.Params, x: jnp.ndarray, cache: AC.KVCache,
               cfg: ModelConfig, positions: jnp.ndarray, seed
               ) -> Tuple[jnp.ndarray, AC.KVCache]:
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    q = _mla_queries(p, x, cfg, positions).reshape(B, H, -1)
    ckv = _mla_cache_stream(p, x, cfg, positions)[:, :, None, :]  # (B,1,1,cw)
    scale = (m.nope_dim + m.rope_dim) ** -0.5
    # same unified SPU op step as GQA; the cache's v_width selects mla_decode
    ctx, cache = OPS.attention_decode_step(cache, ckv, None, q,
                                           cfg.state_quant, scale=scale,
                                           seed=seed)  # (B,H,kv_lora)
    o = jnp.einsum("bhc,hcv->bhv", ctx.astype(x.dtype), p["w_uv"])
    return o.reshape(B, 1, H * m.v_dim) @ p["wo"], cache


def mla_spec_decode(p: L.Params, x: jnp.ndarray, cache: AC.KVCache,
                    cfg: ModelConfig, positions: jnp.ndarray, seed
                    ) -> Tuple[jnp.ndarray, AC.KVCache]:
    """Speculative MLA decode over n positions (see attention_spec_decode)."""
    m = cfg.mla
    B, n, _ = x.shape
    H = cfg.n_heads
    q = _mla_queries(p, x, cfg, positions)                # (B, n, H, cw)
    ckv = _mla_cache_stream(p, x, cfg, positions)[:, :, None, :]  # (B,n,1,cw)
    scale = (m.nope_dim + m.rope_dim) ** -0.5
    ctx, cache = OPS.attention_spec_step(cache, ckv, None, q, cfg.state_quant,
                                         scale=scale, seed=seed)
    o = jnp.einsum("bnhc,hcv->bnhv", ctx.astype(x.dtype), p["w_uv"])
    return o.reshape(B, n, H * m.v_dim) @ p["wo"], cache
