"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one SPU kernel with ``interpret=False`` against a
described (not attached) ``v5e:2x2`` topology and compiles it with the TPU
compiler that ships with jax: what Mosaic refuses here (block shapes off
the 8 x 128 tiling, unsupported casts or reshapes, fast-memory overflow)
would fail the same way on the chip.  Nothing runs, so nothing about
results or times is checked.

Widths: zamba2-2.7b (80 Mamba-2 heads with dk = dv = 64; shared attention
with 32 KV heads of width 80, stacked over 9 layer groups) at decode batch
8, and mamba2-2.7b's state update (dk = 128) at 8 and at 20 rows.
"""
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
KVH, D, GROUPS = 32, 80, 9  # zamba2-2.7b shared attention
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
    pool = lambda: _mx8((PAGES, GROUPS, 128, KVH, D), one_chip)
    _compile(lambda q, k, v, bt, g, n: mx_paged_attention_decode(
                 q, k, v, bt, g, n, interpret=False),
             _sds((B, 32, D), jnp.float32, one_chip), pool(), pool(),
             i32(B, NPG), i32(), i32(B))


def test_paged_kv_append_compiles(one_chip):
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)
    widths = ((D, jnp.int8), (D // 16, jnp.uint8), (D // 16, jnp.uint8))
    pools = [_sds((PAGES, GROUPS, 128, KVH, w), dt, one_chip)
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
    pool = lambda: _mx8((PAGES, GROUPS, 128, KVH, D), one_chip)
    _compile(lambda q, k, v, bt, g, n: mx_paged_spec_attention_decode(
                 q, k, v, bt, g, n, interpret=False),
             _sds((B, KQ, 32, D), jnp.float32, one_chip), pool(), pool(),
             i32(B, NPG), i32(), i32(B))


def test_mx_quantize_compiles(one_chip):
    _compile(lambda x: mx_quantize(x, 0, rounding="stochastic",
                                   interpret=False),
             _sds((512, 2560), jnp.float32, one_chip))
