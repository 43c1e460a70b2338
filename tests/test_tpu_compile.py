"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one SPU kernel with ``interpret=False`` against a
described (not attached) ``v5e:2x2`` topology and compiles it with the TPU
compiler that ships with jax: what Mosaic refuses here (block shapes off
the 8 x 128 tiling, unsupported casts or reshapes, fast-memory overflow)
would fail the same way on the chip.  Nothing runs, so nothing about
results or times is checked.

Widths: zamba2-2.7b's state update (80 Mamba-2 heads with dk = dv = 64)
and paged attention at its published 32 KV heads of 160 stacked over its 9
shared-block applications, and its whole paged decode step at the chat
cell's 20 rows; mamba2-2.7b's state update (dk = 128) at 8 and at 20 rows,
and its whole paged decode step at the chat cell's pool.  The attention
kernels also compile at 32 heads of 80, a width whose exponent rows pad to
more lanes.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import formats as F
from repro.kernels.mx_paged_attention import (mx_paged_attention_decode,
                                              mx_paged_kv_append)
from repro.kernels.mx_quant import mx_quantize
from repro.kernels.mx_spec_attention import (mx_paged_spec_attention_decode,
                                             mx_spec_attention_decode)
from repro.kernels.mx_state_update import mx_state_update

B = 8                      # decode batch
KVH, D, GROUPS = 32, 80, 9  # 32 KV heads of 80 over 9 stacked layers
ZAMBA2_D = 160              # zamba2-2.7b's published head width
PAGES, NPG, KQ = 16, 3, 4   # pool pages, block-table width, verify queries


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mx8(shape, sharding):
    groups = shape[:-1] + (shape[-1] // F.MX8_GROUP,)
    return F.QuantizedTensor("mx8", shape, {
        "mantissa": _sds(shape, jnp.int8, sharding),
        "exponent": _sds(groups, jnp.uint8, sharding),
        "micro": _sds(groups, jnp.uint8, sharding)})


def _mx8_pool(pages, groups, heads, width, sharding):
    """An MX8 page pool as the serving pool stores it: logical shape
    (pages, groups, 128, heads, width), tokens on the last axis."""
    def stored(w, dtype):
        return _sds((pages, groups, heads * w, 128), dtype, sharding)
    return F.QuantizedTensor("mx8", (pages, groups, 128, heads, width), {
        "mantissa": stored(width, jnp.int8),
        "exponent": stored(width // F.MX8_GROUP, jnp.uint8),
        "micro": stored(width // F.MX8_GROUP, jnp.uint8)})


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


@pytest.mark.parametrize("arch,heads,dk,dv,batch", [
    pytest.param("zamba2-2.7b", 80, 64, 64, B, id="zamba2-2.7b-80-64-64"),
    pytest.param("mamba2-2.7b", 80, 128, 64, B, id="mamba2-2.7b-80-128-64"),
    # the mamba2-chat-open cell's decode width
    pytest.param("mamba2-2.7b", 80, 128, 64, 20,
                 id="mamba2-2.7b-80-128-64-20"),
])
def test_state_update_compiles(one_chip, arch, heads, dk, dv, batch):
    f32 = lambda *s: _sds(s, jnp.float32, one_chip)
    _compile(lambda qS, d, k, v, q, seed: mx_state_update(
                 qS, d, k, v, q, seed, rounding="stochastic",
                 interpret=False),
             _mx8((batch, heads, dv, dk), one_chip), f32(batch, heads, dk),
             f32(batch, heads, dk), f32(batch, heads, dv),
             f32(batch, heads, dk),
             _sds((), jnp.int32, one_chip))


def test_paged_attn_decode_compiles(one_chip):
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)
    pool = lambda: _mx8_pool(PAGES, GROUPS, KVH, D, one_chip)
    _compile(lambda q, k, v, bt, g, n: mx_paged_attention_decode(
                 q, k, v, bt, g, n, interpret=False),
             _sds((B, 32, D), jnp.float32, one_chip), pool(), pool(),
             i32(B, NPG), i32(), i32(B))


def test_paged_attn_decode_compiles_at_zamba2_width(one_chip):
    """32 KV heads of 160: every head's rows of a page, dequantized in
    VMEM at once (5,120 rows of 128 tokens for K and for V)."""
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)
    pool = lambda: _mx8_pool(PAGES, GROUPS, KVH, ZAMBA2_D, one_chip)
    _compile(lambda q, k, v, bt, g, n: mx_paged_attention_decode(
                 q, k, v, bt, g, n, scale=(ZAMBA2_D / 2) ** -0.5,
                 interpret=False),
             _sds((20, 32, ZAMBA2_D), jnp.float32, one_chip), pool(), pool(),
             i32(20, 8), i32(), i32(20))


def _paged_decode_step_hlo(arch, sharding, monkeypatch, rows=20,
                           n_pages=141, n_slabs=21):
    """The whole paged decode step of ``arch`` at its published widths over
    a chat cell's pool (8-page block tables), compiled: (HLO text, memory
    analysis, the cache paging).  Shapes only: nothing is allocated."""
    from repro.configs import get_config
    from repro.core.paged import PAGE_TOKENS
    from repro.models import model as M
    from repro.ops import attention, paged_ops, spec_verify, state_update
    from repro.ops.base import StateQuantConfig
    from repro.serving.memory.layout import CachePaging
    for mod in (attention, paged_ops, spec_verify, state_update):
        monkeypatch.setattr(mod, "interpret_pallas", lambda: False)
    cfg = get_config(arch).with_(state_quant=StateQuantConfig(
        fmt="mx8", rounding="stochastic", backend="pallas"))
    paging = CachePaging(M.init_decode_caches(cfg, 1, PAGE_TOKENS),
                         M.abstract_decode_caches(cfg, 2, PAGE_TOKENS),
                         M.abstract_decode_caches(cfg, 1, 2 * PAGE_TOKENS))
    pools = [_sds(((n_pages if s.kind == "page" else n_slabs),)
                  + s.stored_shape, s.dtype, sharding)
             for s in paging.specs]
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, sharding),
        jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg)))
    i32 = lambda *s: _sds(s, jnp.int32, sharding)

    def step(params, pools, bt, slabs, lengths, tokens, seed):
        views = paging.paged_view(pools, bt, slabs, lengths)
        logits, new = M.paged_decode_step(params, cfg=cfg, tokens=tokens,
                                          caches=views, lengths=lengths,
                                          seed=seed)
        return logits, paging.commit(pools, new, slabs)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, i32(rows, 8), i32(rows), i32(rows), i32(rows),
        i32()).compile()
    return compiled.as_text(), compiled.memory_analysis(), paging


#: what may produce a whole state pool or a batch of its rows: the step's
#: parameters and loop plumbing, and the state-update kernel itself
_STATE_POOL_OPS = ("parameter", "get-tuple-element", "tuple", "bitcast",
                   "custom-call", "while", "opt-barrier")


def _state_pool_moves(text, paging, rows=20, n_slabs=21):
    """Instructions whose result is an MX8 state payload pool, or ``rows``
    rows of one (stored, or in the logical ``(rows, H, dv, c)`` order),
    other than the kernel and the plumbing: the copies, gathers, scatters
    and slices of a state path that relays the pool out."""
    shapes = set()
    for s in paging.specs:
        if s.mx8_field:
            lead, stored = s.content_shape[:-3], s.stored_shape[len(
                s.content_shape) - 3:]
            for shape in ((n_slabs,) + s.stored_shape,
                          (n_slabs,) + s.content_shape,
                          (rows,) + stored, (rows,) + s.content_shape[-3:],
                          (rows,) + lead + stored):
                shapes.add(",".join(map(str, shape)))
    found = re.findall(r"= [us]8\[([\d,]+)\]\S* ([\w-]+)\(", text)
    return [(shape, op) for shape, op in found
            if shape in shapes and op not in _STATE_POOL_OPS]


def test_zamba2_paged_decode_step_compiles(one_chip, monkeypatch):
    """The whole paged decode step of zamba2-2.7b at its published widths
    (2.66 B parameters, 54 layers, 9 shared-block applications) over the
    chat cell's pool: 20 rows, 141 pages, 21 slabs, 8-page block tables.
    It fits v5e's 15.75 GB, no K/V page pool is copied into another
    layout around the kernels, and the MX8 state slabs are updated in the
    pool: nothing copies, gathers or scatters them."""
    text, mem, paging = _paged_decode_step_hlo("zamba2-2.7b", one_chip,
                                               monkeypatch)
    for kind in ("state_update", "attn_decode", "kv_append"):
        assert f"spu_{kind}" in text
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    page_copies = re.findall(r"= \w+\[141,\S* copy\(", text)
    assert not page_copies, page_copies
    moves = _state_pool_moves(text, paging)
    assert not moves, moves


def test_mamba2_paged_decode_step_compiles(one_chip, monkeypatch):
    """The whole paged decode step of mamba2-2.7b (64 layers, 80 heads of
    64 with dk 128) over the chat cell's pool: 20 rows, 21 slabs.  It fits
    v5e's 15.75 GB, and the MX8 state slabs are updated in the pool:
    nothing copies, gathers or scatters them."""
    text, mem, paging = _paged_decode_step_hlo("mamba2-2.7b", one_chip,
                                               monkeypatch)
    assert "spu_state_update" in text
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    moves = _state_pool_moves(text, paging)
    assert not moves, moves


def test_paged_kv_append_compiles(one_chip):
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)
    widths = ((D, jnp.int8), (D // 16, jnp.uint8), (D // 16, jnp.uint8))
    pools = [_sds((PAGES, GROUPS, KVH * w, 128), dt, one_chip)
             for w, dt in widths]
    rows = [_sds((B, KVH, w), dt, one_chip) for w, dt in widths]
    _compile(lambda p, r, bt, g, n: mx_paged_kv_append(
                 p, r, bt, g, n, interpret=False),
             pools + pools, rows + rows, i32(B, NPG), i32(), i32(B))


def test_spec_verify_compiles(one_chip):
    cache = lambda: _mx8((B, NPG * 128, KVH, D), one_chip)
    _compile(lambda q, k, v, n: mx_spec_attention_decode(
                 q, k, v, n, interpret=False),
             _sds((B, KQ, 32, D), jnp.float32, one_chip), cache(), cache(),
             _sds((B,), jnp.int32, one_chip))


def test_paged_spec_verify_compiles(one_chip):
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)
    pool = lambda: _mx8_pool(PAGES, GROUPS, KVH, D, one_chip)
    _compile(lambda q, k, v, bt, g, n: mx_paged_spec_attention_decode(
                 q, k, v, bt, g, n, interpret=False),
             _sds((B, KQ, 32, D), jnp.float32, one_chip), pool(), pool(),
             i32(B, NPG), i32(), i32(B))


def test_mx_quantize_compiles(one_chip):
    _compile(lambda x: mx_quantize(x, 0, rounding="stochastic",
                                   interpret=False),
             _sds((512, 2560), jnp.float32, one_chip))
