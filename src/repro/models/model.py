"""Model assembly: block patterns, scan-over-layers, train/prefill/decode.

A model is a repeating ``pattern`` of mixer blocks (attn | mla | mamba2 | gla
| retnet | hgrn2 | mlstm | slstm); a hybrid (Zamba2) also applies shared
attention + MLP blocks on the input of the layers its config lists (see
"hybrid schedule" below).  Parameters of the repeating groups are
stacked along a leading axis and executed with ``jax.lax.scan`` so the HLO
is O(1) in depth (MaxText-style), with per-group remat.

Three step kinds (matching the benchmark shapes):
  * train   -- full-sequence forward + chunked-CE loss
  * prefill -- full-sequence forward that also builds the decode caches
               (quantized KV / recurrent state), returns last-position logits
  * decode  -- one token through the quantized caches (the Pimba fast path)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.models import attention as ATT
from repro.models import layers as L
from repro.models import ssm as SSM
from repro.models.config import ModelConfig

Params = dict

_SSM_KINDS = ("mamba2", "gla", "retnet", "hgrn2", "mlstm", "slstm")
_NO_FFN = ("mamba2", "mlstm", "slstm")   # blocks with internal expansion
_SEED_STRIDE = 1000003


def _has_ffn(cfg: ModelConfig, kind: str) -> bool:
    return cfg.ffn_kind != "none" and kind not in _NO_FFN


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_element(key, cfg: ModelConfig, kind: str, layer_idx: int,
                  dense_ffn: bool = False) -> Params:
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {"norm": L.init_norm(cfg.d_model, cfg.norm_kind, dt)}
    if kind == "attn":
        p["mixer"] = ATT.init_attention(k1, cfg)
    elif kind == "mla":
        p["mixer"] = ATT.init_mla(k1, cfg)
    elif kind == "mamba2":
        p["mixer"] = SSM.init_mamba2(k1, cfg)
    elif kind in ("gla", "retnet", "hgrn2"):
        p["mixer"] = SSM.init_gla_family(k1, cfg, kind)
        if kind == "hgrn2":  # depth-dependent forget-gate lower bound
            p["mixer"]["beta"] = jnp.array(
                [layer_idx / max(cfg.n_layers, 1)], jnp.float32)
    elif kind == "mlstm":
        p["mixer"] = SSM.init_mlstm(k1, cfg)
    elif kind == "slstm":
        p["mixer"] = SSM.init_slstm(k1, cfg)
    else:
        raise ValueError(kind)
    if _has_ffn(cfg, kind):
        kf, kff = jax.random.split(k2)
        p["ffn_norm"] = L.init_norm(cfg.d_model, cfg.norm_kind, dt)
        if cfg.ffn_kind == "moe":
            if dense_ffn:
                p["ffn"] = L.init_ffn(
                    kff, cfg, d_ff=cfg.moe.first_dense_ff or cfg.moe.d_expert)
            else:
                p["ffn"] = L.init_moe(kff, cfg)
        elif dense_ffn:
            p["ffn"] = L.init_ffn(kff, cfg)
        else:
            p["ffn"] = L.init_ffn(kff, cfg)
    return p


def _init_shared_block(key, cfg: ModelConfig) -> Params:
    """One Zamba2 shared block: attention over the stream concatenated with
    the embedding (2 d_model wide) back to d_model, then an MLP with a fused
    gate/up projection."""
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    return {
        "norm": L.init_norm(2 * d, cfg.norm_kind, dt),
        "attn": ATT.init_attention(k1, cfg, d_in=2 * d),
        "ffn_norm": L.init_norm(d, cfg.norm_kind, dt),
        "ffn": {"wi": L.dense_init(k2, d, 2 * cfg.d_ff, dt),
                "wo": L.dense_init(k3, cfg.d_ff, d, dt,
                                   1.0 / np.sqrt(2 * cfg.n_layers))},
    }


def _init_application(key, cfg: ModelConfig) -> Params:
    """One shared-block application's own weights: the linear on the
    block's output and the LoRA on the MLP's gate/up projection."""
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    d, r = cfg.d_model, cfg.adapter_rank
    return {"linear": L.dense_init(k1, d, d, dt),
            "lora_a": L.dense_init(k2, d, r, dt),
            "lora_b": L.dense_init(k3, r, 2 * cfg.d_ff, dt)}


def init_model(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, cfg.n_groups + 6)
    dt = jnp.dtype(cfg.param_dtype)
    params: Params = {}
    if cfg.frontend is None:
        params["embed"] = L.embed_init(keys[-1], cfg.vocab_size, cfg.d_model, dt)
    else:
        params["frontend_proj"] = L.dense_init(
            keys[-1], cfg.frontend_dim, cfg.d_model, dt)
        if cfg.frontend == "patch":   # VLM also embeds text tokens
            params["embed"] = L.embed_init(
                keys[-2], cfg.vocab_size, cfg.d_model, dt)
    if cfg.pos_emb == "learned":
        params["pos"] = L.embed_init(keys[-3], 32768, cfg.d_model, dt)

    if cfg.prelude:
        pks = jax.random.split(keys[-6], len(cfg.prelude))
        params["prelude"] = tuple(
            _init_element(pks[i], cfg, kind, i, dense_ffn=True)
            for i, kind in enumerate(cfg.prelude))

    # stacked group params, vmapped over the group keys: each op writes all
    # groups into the stacked arrays at once, where building the groups one
    # by one and stacking them would hold them twice (2x the model's bytes)
    def one_group(key, gidx):
        eks = jax.random.split(key, len(cfg.pattern))
        return tuple(
            _init_element(eks[i], cfg, kind, gidx * len(cfg.pattern) + i)
            for i, kind in enumerate(cfg.pattern))
    params["groups"] = jax.vmap(one_group)(keys[:cfg.n_groups],
                                           jnp.arange(cfg.n_groups))

    if cfg.shared_attn:
        nb = cfg.n_mem_blocks
        sks = jax.random.split(keys[-4], nb + cfg.n_shared_apps)
        params["shared"] = tuple(_init_shared_block(k, cfg) for k in sks[:nb])
        params["hybrid"] = tuple(_init_application(k, cfg)
                                 for k in sks[nb:])
    params["final_norm"] = L.init_norm(cfg.d_model, cfg.norm_kind, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(keys[-5], cfg.d_model, cfg.vocab_size, dt)
    return params


def _lm_head(params: Params, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# full-sequence block application (train / prefill)
# ---------------------------------------------------------------------------

def _element_forward(p: Params, x, cfg: ModelConfig, kind: str,
                     positions, prefix_len: int, want_cache: bool,
                     mesh_axes, extra=None, lengths=None
                     ) -> Tuple[jnp.ndarray, Any]:
    """One layer; ``extra`` (a shared block's output) is added to its input
    before the norm and not to the residual stream.  ``lengths`` (B,) marks
    each row's positions from there on as padding (see :func:`prefill`)."""
    h = L.apply_norm(p["norm"], x if extra is None else x + extra,
                     cfg.norm_kind, cfg.norm_eps)
    cache = None
    if kind == "attn":
        y = ATT.attention_forward(p["mixer"], h, cfg, positions,
                                  prefix_len=prefix_len)
        if want_cache:
            kv = ATT.attention_prefill_kv(p["mixer"], h, cfg, positions)
            cache = _build_kv_cache(kv[0], kv[1], cfg, lengths=lengths)
    elif kind == "mla":
        y = ATT.mla_forward(p["mixer"], h, cfg, positions)
        if want_cache:
            ckv = ATT._mla_cache_stream(p["mixer"], h, cfg, positions)
            cache = _build_kv_cache(ckv[:, :, None, :], None, cfg,
                                    v_width=cfg.mla.kv_lora)
    elif kind == "mamba2":
        y, st = SSM.mamba2_forward(p["mixer"], h, cfg, par=mesh_axes,
                                   lengths=lengths)
        cache = st if want_cache else None
    elif kind in ("gla", "retnet", "hgrn2"):
        y, st = SSM.gla_family_forward(p["mixer"], h, cfg, kind, par=mesh_axes)
        cache = st if want_cache else None
    elif kind == "mlstm":
        y, st = SSM.mlstm_forward(p["mixer"], h, cfg, par=mesh_axes)
        cache = st if want_cache else None
    elif kind == "slstm":
        y, st = SSM.slstm_forward(p["mixer"], h, cfg, par=mesh_axes)
        cache = st if want_cache else None
    else:
        raise ValueError(kind)
    x = x + y
    if _has_ffn(cfg, kind):
        h = L.apply_norm(p["ffn_norm"], x, cfg.norm_kind, cfg.norm_eps)
        if cfg.ffn_kind == "moe" and "router" in p["ffn"]:
            y = L.apply_moe(p["ffn"], h, cfg, mesh_axes)
        elif cfg.ffn_kind == "moe":
            y = L.apply_ffn(p["ffn"], h, cfg.ffn_kind_inner)
        else:
            y = L.apply_ffn(p["ffn"], h, cfg.ffn_kind)
        x = x + y
    return x, cache


def _build_kv_cache(k: jnp.ndarray, v: Optional[jnp.ndarray],
                    cfg: ModelConfig, v_width: Optional[int] = None,
                    lengths=None) -> AC.KVCache:
    """Quantize full-sequence K/V into a cache with tile-aligned capacity;
    with ``lengths`` (B,), the rows from each row's length on are padding:
    zeroed, and the cache holds ``lengths`` positions."""
    B, S = k.shape[:2]
    if lengths is not None:
        real = L.valid_mask(lengths, S)
        k = jnp.where(real.reshape((B, S) + (1,) * (k.ndim - 2)), k, 0)
        if v is not None:
            v = jnp.where(real.reshape((B, S) + (1,) * (v.ndim - 2)), v, 0)
    cap = -(-S // 128) * 128
    pad = cap - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad)) + ((0, 0),) * (k.ndim - 2))
        if v is not None:
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
    sq = cfg.state_quant
    lengths = (jnp.full((B,), S, jnp.int32) if lengths is None
               else lengths.astype(jnp.int32))
    if sq.quantized:
        qk = F.quantize(k, sq.fmt)
        qv = None if v is None else F.quantize(v, sq.fmt)
        return AC.KVCache(qk, qv, lengths, sq.fmt, v_width)
    dt = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "fp16": jnp.float16}[sq.fmt]
    return AC.KVCache(k.astype(dt), None if v is None else v.astype(dt),
                      lengths, sq.fmt, v_width)


# ---------------------------------------------------------------------------
# hybrid schedule (Zamba2): shared blocks on the input of chosen layers
# ---------------------------------------------------------------------------
#
# Application j of the shared blocks runs before layer
# ``hybrid_layer_ids[j]``: block ``j % n_mem_blocks`` reads the stream x
# concatenated with the embedding x0 (RMSNorm over 2 d_model), attends with
# scores scaled by (head_dim / 2) ** -0.5, and its MLP output goes through
# the application's own linear.  That result is added to the input of the
# layer before its norm -- the stream itself never receives it -- and the
# block has no residual inside.  The layers run in segments, each a scan
# over its layers by index that starts at a hybrid layer; the block output
# rides the carry and is zero after the segment's first layer.

def _block_of(cfg: ModelConfig, app: int) -> int:
    """The shared block that application ``app`` runs: they alternate."""
    return app % cfg.n_mem_blocks


def _segments(cfg: ModelConfig):
    """[(lo, hi, app)]: layers lo..hi-1 run as one scan, the first of them
    after shared-block application ``app`` (None before the first)."""
    bounds = (0,) + cfg.hybrid_layer_ids + (cfg.n_layers,)
    return [(lo, hi, j - 1 if j else None)
            for j, (lo, hi) in enumerate(zip(bounds, bounds[1:])) if hi > lo]


def _shared_scale(cfg: ModelConfig) -> float:
    return (cfg.head_dim / 2) ** -0.5


def _shared_in(p: Params, cfg: ModelConfig, x, x0):
    return L.apply_norm(p["norm"], jnp.concatenate([x, x0], axis=-1),
                        cfg.norm_kind, cfg.norm_eps)


def _shared_out(p: Params, a: Params, cfg: ModelConfig, y):
    h = L.apply_norm(p["ffn_norm"], y, cfg.norm_kind, cfg.norm_eps)
    return L.shared_mlp(p["ffn"], a, h) @ a["linear"]


def _take(tree, i):
    """Row ``i`` of every leaf of a layer-stacked tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _put(tree, rows, i):
    return jax.tree.map(
        lambda a, r: jax.lax.dynamic_update_index_in_dim(
            a, r.astype(a.dtype), i, 0), tree, rows)


def _stack(trees):
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def _scan_segment(body, carry, lo: int, hi: int, cfg: ModelConfig):
    """``body(carry, i)`` over layers lo..hi-1: a scan, or unrolled with
    static indices when ``cfg.scan_layers`` is off (the cost probe)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, jnp.arange(lo, hi))
    ys = []
    for i in range(lo, hi):
        carry, y = body(carry, i)
        ys.append(y)
    return carry, _stack(ys)


def _run_hybrid(params: Params, x, cfg: ModelConfig, positions,
                prefix_len: int, want_cache: bool, mesh_axes, ckpt,
                lengths=None):
    """Full-sequence forward of the hybrid schedule; caches as
    :func:`init_decode_caches` lays them out (layer stack, then the K/V of
    every application stacked)."""
    kind = cfg.pattern[0]
    x0 = x

    def layer(carry, i):
        x, t = carry
        if cfg.seq_parallel:
            x = _seq_shard(x, mesh_axes)
        fn = ckpt(lambda p, xx, tt: _element_forward(
            p[0], xx, cfg, kind, positions, prefix_len, want_cache,
            mesh_axes, extra=tt, lengths=lengths))
        x, c = fn(_take(params["groups"], i), x, t)
        return (x, jnp.zeros_like(t)), (c,)

    def application(p, a, x):
        h = _shared_in(p, cfg, x, x0)
        y = ATT.attention_forward(p["attn"], h, cfg, positions,
                                  prefix_len=prefix_len,
                                  scale=_shared_scale(cfg))
        cache = None
        if want_cache:
            k, v = ATT.attention_prefill_kv(p["attn"], h, cfg, positions)
            cache = _build_kv_cache(k, v, cfg, lengths=lengths)
        return _shared_out(p, a, cfg, y), cache

    ys, kv = [], []
    for lo, hi, app in _segments(cfg):
        t = jnp.zeros_like(x)
        if app is not None:
            t, c = ckpt(application)(params["shared"][_block_of(cfg, app)],
                                     params["hybrid"][app], x)
            kv.append(c)
        (x, _), y = _scan_segment(layer, (x, t), lo, hi, cfg)
        ys.append(y)
    if not want_cache:
        return x, None
    layers = jax.tree.map(lambda *a: jnp.concatenate(a), *ys)
    return x, layers + (_stack(kv),)


def _decode_hybrid(params: Params, cfg: ModelConfig, x, caches, positions,
                   lengths, seed, spec: bool):
    """Decode through the hybrid schedule over dense or paged caches.

    ``x`` (B, n, d) holds one token a row (``spec`` off) or the verify
    positions.  Layer i runs with the seed of the scanned path's group i;
    application j's K/V append with that of its layer plus 99.  Returns
    (x, new caches, per-position state snapshots or None)."""
    from repro.core import paged as PG
    kind = cfg.pattern[0]
    x0 = x
    carried, scanned = PG.split_paged(caches[0])
    kv = caches[1]
    paged = isinstance(kv, PG.PagedKVCache)
    dense_kv = []

    def layer(carry, i):
        x, t, carried, scanned = carry
        seed_i = (jnp.uint32(seed) + jnp.asarray(i, jnp.uint32)
                  * jnp.uint32(_SEED_STRIDE) + jnp.uint32(1))
        c = PG.merge_paged(PG.with_group(carried, i, lengths),
                           _take(scanned, i))
        p = _take(params["groups"], i)[0]
        snap = None
        if spec:
            x, c, snap = _element_spec_decode(p, x, c, cfg, kind, positions,
                                              seed_i, extra=t)
        else:
            x, c = _element_decode(p, x, c, cfg, kind, positions, seed_i,
                                   extra=t)
        ca, sc = PG.split_paged(c)
        return (x, jnp.zeros_like(t), ca, _put(scanned, sc, i)), snap

    snaps = []
    for lo, hi, app in _segments(cfg):
        t = jnp.zeros_like(x)
        if app is not None:
            p = params["shared"][_block_of(cfg, app)]
            h = _shared_in(p, cfg, x, x0)
            c = (PG.with_group(kv, app, lengths) if paged
                 else jax.tree.map(lambda a: a[app], kv))
            aseed = jnp.uint32(seed) + jnp.uint32(_SEED_STRIDE * lo + 99)
            if spec:
                y, c = ATT.attention_spec_decode(p["attn"], h, c, cfg,
                                                 positions, aseed,
                                                 scale=_shared_scale(cfg))
            else:
                y, c = ATT.attention_decode(p["attn"], h, c, cfg,
                                            positions[:, None], aseed,
                                            scale=_shared_scale(cfg))
            t = _shared_out(p, params["hybrid"][app], cfg, y)
            if paged:
                kv = c
            else:
                dense_kv.append(c)
        (x, _, carried, scanned), snap = _scan_segment(
            layer, (x, t, carried, scanned), lo, hi, cfg)
        snaps.append(snap)
    new = (PG.merge_paged(carried, scanned), kv if paged else _stack(dense_kv))
    if not spec:
        return x, new, None
    # (layers, n, B, ...) -> position-major (n, B, layers, ...)
    snap = jax.tree.map(lambda *a: jnp.moveaxis(jnp.concatenate(a), 0, 2),
                        *snaps)
    return x, new, (snap, None)


def _seq_shard(x: jnp.ndarray, par) -> jnp.ndarray:
    """Sequence-parallel constraint on the layer-boundary activations.

    The scan-over-layers carry is the dominant saved residual of the
    backward pass; sharding its sequence dim over the 'model' axis
    (Megatron-SP style) divides that memory by TP.  GSPMD inserts the
    all-gather at attention entry / reduce-scatter at exit.
    """
    if par is None or not hasattr(par, "mesh"):
        return x
    B, S = x.shape[:2]
    if S <= 1 or S % par.tp != 0 or B % par.batch_size_divisor != 0:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, par.named(P(par.batch_axes, par.model_axis, None)))


def _run_blocks(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                positions, prefix_len: int, want_cache: bool,
                mesh_axes, lengths=None) -> Tuple[jnp.ndarray, Any]:
    if cfg.seq_parallel:
        x = _seq_shard(x, mesh_axes)

    prelude_caches = []
    for i, kind in enumerate(cfg.prelude):
        x, c = _element_forward(params["prelude"][i], x, cfg, kind, positions,
                                prefix_len, want_cache, mesh_axes,
                                lengths=lengths)
        prelude_caches.append(c)

    def _maybe_ckpt(fn):
        # nested remat: one element's backward lives at a time, so a group
        # of many elements does not hold every sublayer's cotangents
        # simultaneously
        return jax.checkpoint(fn, prevent_cse=False) if cfg.remat else fn

    if cfg.shared_attn:
        return _run_hybrid(params, x, cfg, positions, prefix_len, want_cache,
                           mesh_axes, _maybe_ckpt, lengths=lengths)

    def group_body(x, ginp):
        gparams, gidx = ginp
        if cfg.seq_parallel:
            x = _seq_shard(x, mesh_axes)
        caches = []
        for pos, kind in enumerate(cfg.pattern):
            fn = _maybe_ckpt(
                lambda p, xx, kind=kind: _element_forward(
                    p, xx, cfg, kind, positions, prefix_len, want_cache,
                    mesh_axes, lengths=lengths))
            x, c = fn(gparams[pos], x)
            caches.append(c)
        return x, tuple(caches)

    body = group_body
    if cfg.remat:
        body = jax.checkpoint(group_body, prevent_cse=False)

    if cfg.scan_layers:
        x, caches = jax.lax.scan(
            body, x, (params["groups"], jnp.arange(cfg.n_groups)))
    else:
        caches_all = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[g], params["groups"])
            x, cs = body(x, (gp, g))
            caches_all.append(cs)
        caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *caches_all)
                  if want_cache else None)
    if cfg.prelude and want_cache:
        caches = {"prelude": tuple(prelude_caches), "groups": caches}
    return x, caches


# ---------------------------------------------------------------------------
# embedding / frontends
# ---------------------------------------------------------------------------

def embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Returns (x, positions, prefix_len)."""
    if cfg.frontend == "patch":           # VLM: [patch embeds ; text tokens]
        patches = batch["patches"] @ params["frontend_proj"]
        tok = params["embed"][batch["tokens"]]
        x = jnp.concatenate([patches, tok], axis=1)
        prefix_len = patches.shape[1]
    elif cfg.frontend == "audio_frames":  # audio: precomputed conv features
        x = batch["frames"] @ params["frontend_proj"]
        prefix_len = 0
    else:
        x = params["embed"][batch["tokens"]]
        prefix_len = cfg.prefix_len
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.pos_emb == "learned":
        x = x + params["pos"][positions]
    elif cfg.pos_emb == "sincos":
        x = x + L.sincos_pos_emb(S, cfg.d_model, x.dtype)[None]
    return x, positions, prefix_len


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def _f32_matmuls(step):
    """Trace a step with f32 matmul precision.

    On a TPU an f32 dot at DEFAULT precision rounds its operands to bf16,
    and XLA hoists those converts of the stacked group weights out of the
    layer scan: a bf16 copy of every scanned weight lives through the step
    (4.2 GiB for zamba2-2.7b in f32).  The models compute in f32, so they
    contract in f32; CPU dots are f32 either way.
    """
    @functools.wraps(step)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return step(*args, **kwargs)
    return traced


@_f32_matmuls
def train_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
               mesh_axes=None) -> jnp.ndarray:
    x, positions, prefix_len = embed_inputs(params, cfg, batch)
    x, _ = _run_blocks(params, x, cfg, positions, prefix_len,
                       want_cache=False, mesh_axes=mesh_axes)
    x = L.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    labels = batch["targets"]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    if cfg.frontend == "patch":
        # loss over text positions only; hidden states are offset by prefix
        x = x[:, -labels.shape[1]:]
    return L.chunked_softmax_xent(x, _lm_head(params, cfg), labels,
                                  mask.astype(jnp.float32), cfg.logit_chunk,
                                  unroll=cfg.cost_probe)


#: the layer kinds whose full-sequence forward masks padded positions
_MASKED_KINDS = frozenset({"attn", "mamba2"})


def masked_prefill_ok(cfg: ModelConfig) -> bool:
    """Whether :func:`prefill` with ``lengths`` gives what the unpadded
    prefill gives: every layer masks the padding (causal attention; Mamba-2
    with ``dt`` 0), and nothing lets a padded position reach a real one --
    no bidirectional prefix, frontend or capacity-bound expert routing."""
    return (set(cfg.pattern) | set(cfg.prelude) <= _MASKED_KINDS
            and cfg.ffn_kind != "moe" and cfg.frontend is None
            and cfg.prefix_len == 0 and not cfg.encoder_only)


@_f32_matmuls
def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            mesh_axes=None, lengths=None) -> Tuple[jnp.ndarray, Any]:
    """Full-sequence forward; returns (last-position logits, caches).

    ``lengths`` (B,), traced: row b holds ``lengths[b]`` real tokens and
    padding after them (one program a padded length, whatever the real
    ones).  The padding leaves the caches untouched -- the recurrent state
    and conv windows end at the last real token, K/V rows past it are zero
    and the K/V lengths are ``lengths`` -- and the logits are those of the
    last real position.  Needs :func:`masked_prefill_ok`."""
    if lengths is not None and not masked_prefill_ok(cfg):
        raise ValueError(f"{cfg.name}: prefill cannot mask padding")
    x, positions, prefix_len = embed_inputs(params, cfg, batch)
    x, caches = _run_blocks(params, x, cfg, positions, prefix_len,
                            want_cache=not cfg.encoder_only,
                            mesh_axes=mesh_axes, lengths=lengths)
    x = L.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    if cfg.encoder_only:
        # encoder models: per-position classification logits
        logits = x @ _lm_head(params, cfg)
        return logits, None
    if lengths is None:
        last = x[:, -1]
    else:
        last = jnp.take_along_axis(x, (lengths - 1)[:, None, None],
                                   axis=1)[:, 0]
    logits = last @ _lm_head(params, cfg)
    return logits, caches


def init_decode_caches(cfg: ModelConfig, B: int, cache_capacity: int) -> Any:
    """Zeroed caches for decode-from-scratch (dry-run decode cells)."""
    def one_element(kind):
        if kind == "attn":
            return AC.init_kv_cache(B, cache_capacity, cfg.n_kv_heads,
                                    cfg.head_dim, cfg.state_quant)
        if kind == "mla":
            return AC.init_kv_cache(B, cache_capacity, 1,
                                    cfg.mla.cache_width, cfg.state_quant,
                                    mla_v_width=cfg.mla.kv_lora)
        if kind == "mamba2":
            return SSM.mamba2_init_state(B, cfg)
        if kind in ("gla", "retnet", "hgrn2"):
            return SSM.gla_family_init_state(B, cfg)
        if kind == "mlstm":
            return SSM.mlstm_init_state(B, cfg)
        if kind == "slstm":
            return SSM.slstm_init_state(B, cfg)
        raise ValueError(kind)

    def stack(tree, n):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)

    stacked = stack(tuple(one_element(k) for k in cfg.pattern), cfg.n_groups)
    if cfg.shared_attn:
        # the K/V of every shared-block application, stacked
        stacked += (stack(AC.init_kv_cache(B, cache_capacity, cfg.n_kv_heads,
                                           cfg.head_dim, cfg.state_quant),
                          cfg.n_shared_apps),)
    if cfg.prelude:
        return {"prelude": tuple(one_element(k) for k in cfg.prelude),
                "groups": stacked}
    return stacked


def abstract_decode_caches(cfg: ModelConfig, B: int, cache_capacity: int) -> Any:
    """Shape/dtype skeleton of :func:`init_decode_caches` without allocating.

    The paged memory pool (``serving/memory``) probes this at several (B, T)
    points to locate every leaf's batch and time axis exactly.
    """
    return jax.eval_shape(lambda: init_decode_caches(cfg, B, cache_capacity))


def set_cache_lengths(caches: Any, lengths: jnp.ndarray) -> Any:
    """Overwrite every KVCache.lengths leaf (e.g. decode over a warm cache)."""
    def fix(c):
        if isinstance(c, AC.KVCache):
            return AC.KVCache(c.k, c.v, jnp.broadcast_to(lengths, c.lengths.shape),
                              c.fmt, c.v_width, c.time_axis)
        return c
    return jax.tree.map(fix, caches,
                        is_leaf=lambda x: isinstance(x, AC.KVCache))


def _element_decode(p: Params, x, cache, cfg: ModelConfig, kind: str,
                    positions, seed, extra=None) -> Tuple[jnp.ndarray, Any]:
    h = L.apply_norm(p["norm"], x if extra is None else x + extra,
                     cfg.norm_kind, cfg.norm_eps)
    if kind == "attn":
        y, cache = ATT.attention_decode(p["mixer"], h, cache, cfg,
                                        positions[:, None], seed)
    elif kind == "mla":
        y, cache = ATT.mla_decode(p["mixer"], h, cache, cfg,
                                  positions[:, None], seed)
    elif kind == "mamba2":
        y, cache = SSM.mamba2_decode(p["mixer"], h, cache, cfg, seed)
    elif kind in ("gla", "retnet", "hgrn2"):
        y, cache = SSM.gla_family_decode(p["mixer"], h, cache, cfg, kind, seed)
    elif kind == "mlstm":
        y, cache = SSM.mlstm_decode(p["mixer"], h, cache, cfg, seed)
    elif kind == "slstm":
        y, cache = SSM.slstm_decode(p["mixer"], h, cache, cfg, seed)
    else:
        raise ValueError(kind)
    x = x + y
    if _has_ffn(cfg, kind):
        h = L.apply_norm(p["ffn_norm"], x, cfg.norm_kind, cfg.norm_eps)
        if cfg.ffn_kind == "moe" and "router" in p["ffn"]:
            y = L.apply_moe(p["ffn"], h, cfg, None)
        elif cfg.ffn_kind == "moe":
            y = L.apply_ffn(p["ffn"], h, cfg.ffn_kind_inner)
        else:
            y = L.apply_ffn(p["ffn"], h, cfg.ffn_kind)
        x = x + y
    return x, cache


@_f32_matmuls
def decode_step(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                caches: Any, lengths: jnp.ndarray, seed=0,
                mesh_axes=None) -> Tuple[jnp.ndarray, Any]:
    """One decode step.  tokens: (B,) int32; lengths: (B,) positions so far.

    Returns (logits (B, V), new caches).
    """
    assert not cfg.encoder_only, f"{cfg.name} is encoder-only: no decode step"
    x = params["embed"][tokens][:, None]                       # (B,1,d)
    positions = lengths
    if cfg.pos_emb == "learned":
        x = x + params["pos"][positions][:, None]
    if cfg.shared_attn:
        x, new_caches, _ = _decode_hybrid(params, cfg, x, caches, positions,
                                          lengths, seed, spec=False)
        return _head(params, cfg, x[:, 0]), new_caches

    if cfg.prelude:
        prelude_caches, caches = caches["prelude"], caches["groups"]
        new_prelude = []
        for i, kind in enumerate(cfg.prelude):
            x, c = _element_decode(params["prelude"][i], x, prelude_caches[i],
                                   cfg, kind, positions,
                                   jnp.uint32(seed) + jnp.uint32(7919 * (i + 1)))
            new_prelude.append(c)

    def group_body(x, ginp):
        gparams, gcaches, gidx = ginp
        seed_g = jnp.uint32(seed) + gidx.astype(jnp.uint32) * jnp.uint32(_SEED_STRIDE)
        new_caches = []
        for pos, kind in enumerate(cfg.pattern):
            x, c = _element_decode(gparams[pos], x, gcaches[pos], cfg, kind,
                                   positions, seed_g + jnp.uint32(pos + 1))
            new_caches.append(c)
        return x, tuple(new_caches)

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(
            group_body, x, (params["groups"], caches, jnp.arange(cfg.n_groups)))
    else:
        ncs = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[g], params["groups"])
            gc = jax.tree.map(lambda a: a[g], caches,
                              is_leaf=lambda x: isinstance(x, jnp.ndarray))
            x, cs = group_body(x, (gp, gc, jnp.asarray(g)))
            ncs.append(cs)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)

    if cfg.prelude:
        new_caches = {"prelude": tuple(new_prelude), "groups": new_caches}
    return _head(params, cfg, x[:, 0]), new_caches


def _head(params: Params, cfg: ModelConfig, x):
    x = L.apply_norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    return x @ _lm_head(params, cfg)


@_f32_matmuls
def paged_decode_step(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                      caches: Any, lengths: jnp.ndarray, seed=0,
                      mesh_axes=None) -> Tuple[jnp.ndarray, Any]:
    """One decode step over block-table-native paged cache views.

    ``caches`` mirrors :func:`init_decode_caches`' structure, but KV caches
    are :class:`~repro.core.paged.PagedKVCache` views and recurrent ``"S"``
    leaves are :class:`~repro.core.paged.PagedState` views -- both address
    the serving pool's shared page/slab pools and carry a ``group`` index
    into the scan-over-layers stack; remaining slab leaves (conv tails,
    sLSTM carries) are dense gathered rows in the stacked ``(G, B, ...)``
    layout.  Because the pools cannot be sliced along the group axis without
    copying them, the paged containers ride the scan *carry* (each group
    iteration re-binds ``group`` and updates the same pools in place) while
    the dense leaves scan as xs/ys exactly like :func:`decode_step`.

    Element math, seeds and op dispatch are shared with :func:`decode_step`
    (the container type selects the paged ops), so logits are bit-identical
    to running the dense path over gathered pages.
    """
    from repro.core import paged as PG
    assert not cfg.encoder_only, f"{cfg.name} is encoder-only: no decode step"
    x = params["embed"][tokens][:, None]                       # (B,1,d)
    positions = lengths
    if cfg.pos_emb == "learned":
        x = x + params["pos"][positions][:, None]
    if cfg.shared_attn:
        x, new_caches, _ = _decode_hybrid(params, cfg, x, caches, positions,
                                          lengths, seed, spec=False)
        return _head(params, cfg, x[:, 0]), new_caches

    if cfg.prelude:
        prelude_caches, caches = caches["prelude"], caches["groups"]
        new_prelude = []
        for i, kind in enumerate(cfg.prelude):
            c = PG.with_group(prelude_caches[i], 0, lengths)
            x, c = _element_decode(params["prelude"][i], x, c,
                                   cfg, kind, positions,
                                   jnp.uint32(seed) + jnp.uint32(7919 * (i + 1)))
            new_prelude.append(c)

    n_elems = len(cfg.pattern)
    carried, scanned = [], []
    for pos in range(n_elems):
        ca, sc = PG.split_paged(caches[pos])
        carried.append(ca)
        scanned.append(sc)
    carried, scanned = tuple(carried), tuple(scanned)

    def group_body(carry, ginp):
        x, kv = carry
        gparams, gstates, gidx = ginp
        seed_g = jnp.uint32(seed) + gidx.astype(jnp.uint32) * jnp.uint32(_SEED_STRIDE)
        new_kv, new_states = [], []
        for pos, kind in enumerate(cfg.pattern):
            c = PG.merge_paged(PG.with_group(kv[pos], gidx, lengths),
                               gstates[pos])
            x, c = _element_decode(gparams[pos], x, c, cfg, kind,
                                   positions, seed_g + jnp.uint32(pos + 1))
            ca, sc = PG.split_paged(c)
            new_kv.append(ca)
            new_states.append(sc)
        return (x, tuple(new_kv)), tuple(new_states)

    if cfg.scan_layers:
        (x, carried), new_scanned = jax.lax.scan(
            group_body, (x, carried),
            (params["groups"], scanned, jnp.arange(cfg.n_groups)))
    else:
        stacked = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[g], params["groups"])
            gs = jax.tree.map(lambda a: a[g], scanned,
                              is_leaf=lambda v: isinstance(v, jnp.ndarray))
            (x, carried), sc = group_body((x, carried),
                                          (gp, gs, jnp.asarray(g)))
            stacked.append(sc)
        new_scanned = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)

    new_caches = tuple(PG.merge_paged(carried[pos], new_scanned[pos])
                       for pos in range(n_elems))
    if cfg.prelude:
        new_caches = {"prelude": tuple(new_prelude), "groups": new_caches}
    return _head(params, cfg, x[:, 0]), new_caches


# ---------------------------------------------------------------------------
# speculative decode: multi-position step with per-position state snapshots
# ---------------------------------------------------------------------------

def _state_snapshot(cache: Any) -> Any:
    """Per-request rows of every recurrent-state leaf of one element's cache.

    PagedState views contribute their viewed slab rows ``pool[slabs, group]``
    ((B, ...); quantized pools yield a plain ``{field: rows}`` dict), dense
    residual leaves (conv tails, sLSTM carries) contribute themselves, and
    KV caches contribute nothing (rejecting drafted tokens only needs the
    host length reset -- the garbage rows are masked and later overwritten).
    """
    from repro.core import paged as PG

    def snap(leaf):
        if isinstance(leaf, PG.PagedState):
            grp = jnp.asarray(leaf.group, jnp.int32)
            if isinstance(leaf.pool, F.QuantizedTensor):
                return {f: a[leaf.slabs, grp]
                        for f, a in leaf.pool.payload.items()}
            return leaf.pool[leaf.slabs, grp]
        if isinstance(leaf, PG.PagedKVCache):
            return None
        return leaf

    return jax.tree.map(snap, cache, is_leaf=PG.is_paged)


def _element_spec_decode(p: Params, x, cache, cfg: ModelConfig, kind: str,
                         positions, seed, extra=None
                         ) -> Tuple[jnp.ndarray, Any, Any]:
    """Multi-position twin of :func:`_element_decode`.

    ``x`` is (B, n, d) -- the current token plus the drafted ones --
    and ``positions`` the (B, n) absolute positions.  Attention scores all
    n positions in one ``spec_verify`` pass over a single cache stream;
    recurrent mixers advance sequentially through the n rows (the state
    update is inherently serial) with the exact per-position seed
    ``seed + i`` of n sequential decode steps, recording a state snapshot
    after each position so rejected drafts can be rolled back bit-exactly.

    Returns ``(x, cache, snap)`` where ``snap`` stacks the per-position
    snapshots to (n, B, ...) leaves (None for attention elements).
    """
    n = x.shape[1]
    h = L.apply_norm(p["norm"], x if extra is None else x + extra,
                     cfg.norm_kind, cfg.norm_eps)
    if kind == "attn":
        y, cache = ATT.attention_spec_decode(p["mixer"], h, cache, cfg,
                                             positions, seed)
        snap = None
    elif kind == "mla":
        y, cache = ATT.mla_spec_decode(p["mixer"], h, cache, cfg,
                                       positions, seed)
        snap = None
    else:
        ys, rows = [], []
        for i in range(n):
            hi = h[:, i:i + 1]
            si = seed + jnp.uint32(i)
            if kind == "mamba2":
                yi, cache = SSM.mamba2_decode(p["mixer"], hi, cache, cfg, si)
            elif kind in ("gla", "retnet", "hgrn2"):
                yi, cache = SSM.gla_family_decode(p["mixer"], hi, cache, cfg,
                                                  kind, si)
            elif kind == "mlstm":
                yi, cache = SSM.mlstm_decode(p["mixer"], hi, cache, cfg, si)
            elif kind == "slstm":
                yi, cache = SSM.slstm_decode(p["mixer"], hi, cache, cfg, si)
            else:
                raise ValueError(kind)
            ys.append(yi)
            # the next position updates the slab pool in place: order the
            # snapshot's reads before it, so the pool is not copied
            cache, snap = jax.lax.optimization_barrier(
                (cache, _state_snapshot(cache)))
            rows.append(snap)
        y = jnp.concatenate(ys, axis=1)
        snap = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
    x = x + y
    if _has_ffn(cfg, kind):
        h = L.apply_norm(p["ffn_norm"], x, cfg.norm_kind, cfg.norm_eps)
        if cfg.ffn_kind == "moe" and "router" in p["ffn"]:
            y = L.apply_moe(p["ffn"], h, cfg, None)
        elif cfg.ffn_kind == "moe":
            y = L.apply_ffn(p["ffn"], h, cfg.ffn_kind_inner)
        else:
            y = L.apply_ffn(p["ffn"], h, cfg.ffn_kind)
        x = x + y
    return x, cache, snap


@_f32_matmuls
def paged_spec_decode_step(params: Params, cfg: ModelConfig,
                           tokens: jnp.ndarray, caches: Any,
                           lengths: jnp.ndarray, seed=0, mesh_axes=None
                           ) -> Tuple[jnp.ndarray, Any, Any]:
    """Speculative verify step: n positions per row through the paged caches.

    tokens (B, n) holds each row's current token followed by its drafted
    (or garbage padding) tokens; lengths (B,) count positions *before* this
    step.  Structure, carry discipline and every element seed mirror
    :func:`paged_decode_step` exactly -- position i of a row runs with the
    seeds of the sequential decode step ``seed + i`` -- so row i's logits
    are bit-identical to decoding the same tokens one step at a time.

    Returns ``(logits (B, n, V), new_caches, snaps)``.  ``snaps`` mirrors
    the cache-tree structure with per-position recurrent-state rows
    normalized to (n, B, ...) leaves ((n, B, G, ...) for scanned groups);
    the engine commits ``snaps[sel]`` to roll rejected positions back.
    """
    from repro.core import paged as PG
    assert not cfg.encoder_only, f"{cfg.name} is encoder-only: no decode step"
    B, n = tokens.shape
    x = params["embed"][tokens]                                # (B,n,d)
    positions = lengths[:, None] + jnp.arange(n, dtype=lengths.dtype)[None]
    if cfg.pos_emb == "learned":
        x = x + params["pos"][positions]
    if cfg.shared_attn:
        x, new_caches, snaps = _decode_hybrid(params, cfg, x, caches,
                                              positions, lengths, seed,
                                              spec=True)
        return _head(params, cfg, x), new_caches, snaps

    if cfg.prelude:
        prelude_caches, caches = caches["prelude"], caches["groups"]
        new_prelude, prelude_snaps = [], []
        for i, kind in enumerate(cfg.prelude):
            c = PG.with_group(prelude_caches[i], 0, lengths)
            x, c, sn = _element_spec_decode(
                params["prelude"][i], x, c, cfg, kind, positions,
                jnp.uint32(seed) + jnp.uint32(7919 * (i + 1)))
            new_prelude.append(c)
            prelude_snaps.append(sn)

    n_elems = len(cfg.pattern)
    carried, scanned = [], []
    for pos in range(n_elems):
        ca, sc = PG.split_paged(caches[pos])
        carried.append(ca)
        scanned.append(sc)
    carried, scanned = tuple(carried), tuple(scanned)

    def group_body(carry, ginp):
        x, kv = carry
        gparams, gstates, gidx = ginp
        seed_g = jnp.uint32(seed) + gidx.astype(jnp.uint32) * jnp.uint32(_SEED_STRIDE)
        new_kv, new_states, gsnaps = [], [], []
        for pos, kind in enumerate(cfg.pattern):
            c = PG.merge_paged(PG.with_group(kv[pos], gidx, lengths),
                               gstates[pos])
            x, c, sn = _element_spec_decode(gparams[pos], x, c, cfg, kind,
                                            positions,
                                            seed_g + jnp.uint32(pos + 1))
            ca, sc = PG.split_paged(c)
            new_kv.append(ca)
            new_states.append(sc)
            gsnaps.append(sn)
        return (x, tuple(new_kv)), (tuple(new_states), tuple(gsnaps))

    if cfg.scan_layers:
        (x, carried), (new_scanned, gsnaps) = jax.lax.scan(
            group_body, (x, carried),
            (params["groups"], scanned, jnp.arange(cfg.n_groups)))
    else:
        stacked = []
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[g], params["groups"])
            gs = jax.tree.map(lambda a: a[g], scanned,
                              is_leaf=lambda v: isinstance(v, jnp.ndarray))
            (x, carried), ys = group_body((x, carried),
                                          (gp, gs, jnp.asarray(g)))
            stacked.append(ys)
        new_scanned, gsnaps = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)

    # scan ys stack per-group snapshots as (G, n, B, ...); normalize every
    # snapshot leaf to position-major (n, B, G, ...) for selection
    gsnaps = jax.tree.map(lambda a: jnp.moveaxis(a, 0, 2), gsnaps)

    new_caches = tuple(PG.merge_paged(carried[pos], new_scanned[pos])
                       for pos in range(n_elems))
    snaps: Any = tuple(gsnaps)
    if cfg.prelude:
        new_caches = {"prelude": tuple(new_prelude), "groups": new_caches}
        snaps = {"prelude": tuple(prelude_snaps), "groups": snaps}
    return _head(params, cfg, x), new_caches, snaps
