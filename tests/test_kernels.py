"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bitwise state.

The backend-parity sweep at the bottom iterates the SPU op REGISTRY rather
than a hardcoded kernel list: for every (op kind, format) with more than one
registered backend, all backends must produce bit-identical packed state and
matching outputs.  Registering a new backend automatically enrolls it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops as OPS
from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.kernels import ref
from repro.kernels.mx_attention import mx_attention_decode
from repro.kernels.mx_quant import mx_quantize
from repro.kernels.mx_state_update import (VMEM_BUDGET, block_bytes,
                                           mx_state_update, plan_blocks)
from repro.ops import interpret_pallas


def _su_inputs(B, H, dk, dv, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    S0 = jax.random.normal(ks[0], (B, H, dv, dk), dtype)
    d = jax.nn.sigmoid(jax.random.normal(ks[1], (B, H, dk), dtype))
    k = jax.random.normal(ks[2], (B, H, dk), dtype)
    v = jax.random.normal(ks[3], (B, H, dv), dtype)
    q = jax.random.normal(ks[4], (B, H, dk), dtype)
    return F.mx8_quantize(S0), d, k, v, q


@pytest.mark.parametrize("B,H,dk,dv", [
    (1, 1, 16, 16),        # minimum tile
    (2, 3, 128, 64),       # mamba2-like (N=128, P=64)
    (1, 2, 64, 128),       # zamba-like
    (2, 1, 256, 512),      # retnet-like
    (1, 1, 128, 1040),     # mlstm-like augmented dv
    (3, 5, 128, 64),       # B·H = 15: no divisor but itself past 5
    (2, 80, 128, 64),      # mamba2 heads: several blocks of pairs
    (2, 10, 256, 512),     # retnet width, vector decay, two heads a step
    (1, 1, 1024, 1024),    # a head past the budget: 128-row tiles
])
@pytest.mark.parametrize("rounding", ["nearest", "stochastic"])
def test_state_update_kernel_bitwise(B, H, dk, dv, rounding):
    qS, d, k, v, q = _su_inputs(B, H, dk, dv)
    qr, yr = ref.quantized_state_update_stored_ref(
        qS, d, k, v, q, rounding=rounding, seed=11)
    qk, yk = mx_state_update(qS, d, k, v, q, seed=11, rounding=rounding,
                             interpret=interpret_pallas())
    for f in ("mantissa", "exponent", "micro"):
        assert jnp.array_equal(qr.payload[f], qk.payload[f]), f
    np.testing.assert_allclose(yr, yk, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16])
def test_state_update_kernel_dtypes(in_dtype):
    qS, d, k, v, q = _su_inputs(2, 2, 128, 64, dtype=in_dtype)
    qk, yk = mx_state_update(qS, d, k, v, q, seed=0,
                             interpret=interpret_pallas())
    assert yk.dtype == jnp.float32
    assert jnp.all(jnp.isfinite(yk))


def test_state_update_scalar_decay_broadcast():
    qS, d, k, v, q = _su_inputs(2, 2, 128, 64)
    d_scalar = d[..., :1]
    q1, y1 = mx_state_update(qS, d_scalar, k, v, q, seed=3,
                             interpret=interpret_pallas())
    d_full = jnp.broadcast_to(d_scalar, d.shape)
    q2, y2 = mx_state_update(qS, d_full, k, v, q, seed=3,
                             interpret=interpret_pallas())
    assert jnp.array_equal(q1.payload["mantissa"], q2.payload["mantissa"])
    np.testing.assert_allclose(y1, y2, rtol=1e-6)


# (heads, dk, dv) of every family that decodes through the kernel
_SU_FAMILIES = {
    "mamba2": (80, 128, 64), "zamba2": (80, 64, 64), "retnet": (10, 256, 512),
    "gla": (4, 320, 640), "hgrn2": (20, 128, 128), "mlstm": (4, 1024, 1040),
}


@pytest.mark.parametrize("family", sorted(_SU_FAMILIES))
@pytest.mark.parametrize("B", [1, 8, 20])
def test_state_update_block_rule(family, B):
    """Each grid step takes the most heads of one batch row that divide H,
    fit the fast-memory budget and span whole 128-lane columns of the
    stored group bytes, for every family's shape; a call of B rows runs B
    times one row's steps over every (row, head) pair once."""
    H, dk, dv = _SU_FAMILIES[family]
    plan = plan_blocks(H, dv, dk)
    if family == "mlstm":
        # 1040 rows of 1024 have no lane-aligned tile: the op runs the
        # reference
        assert plan is None
        return
    f, dv_blk, rows, grid = plan
    width, stored_rows = f * dk, dv // f
    assert f * dk <= max(dk, 128) and stored_rows % dv_blk == 0
    assert H % rows == 0
    assert block_bytes(rows, dv_blk, width) <= VMEM_BUDGET
    assert grid == (H // rows, stored_rows // dv_blk)
    assert B * grid[0] * rows == B * H
    lane_dense = lambda r: r == H or (r * dv_blk) % 128 == 0
    assert lane_dense(rows) or dv_blk < stored_rows
    larger = [r for r in range(rows + 1, H + 1)
              if H % r == 0 and lane_dense(r)]
    assert all(block_bytes(r, dv_blk, width) > VMEM_BUDGET for r in larger)


def test_state_update_block_rule_at_the_chat_cell():
    """mamba2-2.7b at the chat cell: 40 heads a step, 2 steps a row;
    zamba2-2.7b's dk 64 stores two rows a 128-lane row, all 80 heads a
    step."""
    assert plan_blocks(80, 64, 128) == (1, 64, 40, (2, 1))
    assert plan_blocks(80, 64, 64) == (2, 32, 80, (1, 1))


def test_state_update_multi_step_matches_ref():
    """Several chained steps stay bitwise equal (SR counters line up)."""
    qS, d, k, v, q = _su_inputs(1, 2, 64, 32)
    qR = qS
    for step in range(5):
        qS, _ = mx_state_update(qS, d, k, v, q, seed=step,
                                interpret=interpret_pallas())
        qR, _ = ref.quantized_state_update_stored_ref(
            qR, d, k, v, q, rounding="stochastic", seed=step)
    assert jnp.array_equal(qS.payload["mantissa"], qR.payload["mantissa"])


@pytest.mark.parametrize("B,H,KVH,dh,T,t_blk", [
    (1, 4, 4, 64, 128, 128),     # MHA
    (2, 8, 2, 128, 256, 64),     # GQA G=4
    (1, 15, 5, 64, 256, 128),    # smollm heads (G=3)
])
def test_attention_kernel_vs_ref(B, H, KVH, dh, T, t_blk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, dh))
    K = jax.random.normal(ks[1], (B, T, KVH, dh))
    V = jax.random.normal(ks[2], (B, T, KVH, dh))
    lengths = jnp.arange(1, B + 1) * (T // (B + 1)) + 1
    qK, qV = F.mx8_quantize(K), F.mx8_quantize(V)
    y_ref = ref.mx_attention_decode_ref(q, qK, qV, lengths)
    y_k = mx_attention_decode(q, qK, qV, lengths, t_block=t_blk,
                              interpret=interpret_pallas())
    np.testing.assert_allclose(y_ref, y_k, rtol=2e-4, atol=2e-5)


def test_attention_kernel_mla_mode():
    B, H, dkc, vw, T = 2, 16, 192, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.random.normal(ks[0], (B, H, dkc))
    C = jax.random.normal(ks[1], (B, T, 1, dkc))
    qC = F.mx8_quantize(C)
    lengths = jnp.array([200, 64], jnp.int32)
    y = mx_attention_decode(q, qC, None, lengths, v_width=vw,
                            interpret=interpret_pallas())
    kf = F.dequantize(qC)
    y_ref = ref.attention_decode_ref(q, kf, kf[..., :vw], lengths,
                                     scale=dkc ** -0.5)
    np.testing.assert_allclose(y_ref, y, rtol=2e-4, atol=2e-5)


def test_attention_kernel_respects_lengths():
    """Entries beyond `lengths` must not contribute."""
    B, H, KVH, dh, T = 1, 2, 2, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, dh))
    K = jax.random.normal(ks[1], (B, T, KVH, dh))
    V = jax.random.normal(ks[2], (B, T, KVH, dh))
    L = 100
    y1 = mx_attention_decode(q, F.mx8_quantize(K), F.mx8_quantize(V),
                             jnp.array([L]), interpret=interpret_pallas())
    K2 = K.at[:, L:].set(99.0)
    V2 = V.at[:, L:].set(-99.0)
    y2 = mx_attention_decode(q, F.mx8_quantize(K2), F.mx8_quantize(V2),
                             jnp.array([L]), interpret=interpret_pallas())
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rounding", ["nearest", "stochastic"])
@pytest.mark.parametrize("shape", [(16, 64), (300, 128), (5, 7, 32)])
def test_quant_kernel_bitwise(rounding, shape):
    x = jax.random.normal(jax.random.PRNGKey(3), shape)
    qk = mx_quantize(x, seed=9, rounding=rounding, row_block=64,
                     interpret=interpret_pallas())
    qr = ref.mx_quantize_ref(x, rounding=rounding, seed=9)
    for f in ("mantissa", "exponent", "micro"):
        assert jnp.array_equal(qk.payload[f], qr.payload[f]), f


# ---------------------------------------------------------------------------
# registry-driven backend parity: every (op kind, format) with >1 backend
# ---------------------------------------------------------------------------

def _multi_backend_cases():
    """Dense-layout (kind, fmt) pairs with more than one registered backend.

    The paged-layout backends get the same treatment in
    ``tests/test_paged_decode.py``, which also pins them bit-identical to
    the dense-gather path end to end.
    """
    cases = {}
    for kind, backend, fmt, layout in OPS.registered():
        if layout == "dense":
            cases.setdefault((kind, fmt), set()).add(backend)
    return sorted((k, f, tuple(sorted(bs)))
                  for (k, f), bs in cases.items() if len(bs) > 1)


PARITY_CASES = _multi_backend_cases()


def _assert_state_identical(a, b, ctx):
    if isinstance(a, F.QuantizedTensor):
        for f in a.payload:
            assert jnp.array_equal(a.payload[f], b.payload[f]), (ctx, f)
    else:
        assert jnp.array_equal(a, b), ctx


@pytest.mark.parametrize("kind,fmt,backends", PARITY_CASES,
                         ids=[f"{k}-{f}" for k, f, _ in PARITY_CASES])
@pytest.mark.parametrize("rounding", ["nearest", "stochastic"])
def test_registry_backend_parity(kind, fmt, backends, rounding):
    """All registered backends of a (kind, fmt) agree: bit-identical packed
    state, matching outputs."""
    B, H, KVH, dk, dv, T = 2, 4, 2, 64, 32, 128
    results = []
    for backend in backends:
        cfg = OPS.StateQuantConfig(fmt=fmt, rounding=rounding,
                                   backend=backend)
        assert OPS.resolve_backend(kind, fmt, backend, strict=True) == backend
        if kind == "state_update":
            S0 = OPS.init_state(B, H, dk, dv, cfg)
            ks = jax.random.split(jax.random.PRNGKey(0), 4)
            d = jax.nn.sigmoid(jax.random.normal(ks[0], (B, H, dk)))
            k = jax.random.normal(ks[1], (B, H, dk))
            v = jax.random.normal(ks[2], (B, H, dv))
            q = jax.random.normal(ks[3], (B, H, dk))
            Sn, y = OPS.state_update_step(S0, d, k, v, q, cfg, seed=11)
            results.append((backend, Sn, y))
        elif kind in ("attn_decode", "mla_decode"):
            ks = jax.random.split(jax.random.PRNGKey(1), 3)
            if kind == "mla_decode":
                cache = AC.init_kv_cache(B, T, 1, dk + dv, cfg,
                                         mla_v_width=dk)
                kv, vv = jax.random.normal(ks[0], (B, 1, 1, dk + dv)), None
                q = jax.random.normal(ks[1], (B, H, dk + dv))
            else:
                cache = AC.init_kv_cache(B, T, KVH, dk, cfg)
                kv = jax.random.normal(ks[0], (B, 1, KVH, dk))
                vv = jax.random.normal(ks[2], (B, 1, KVH, dk))
                q = jax.random.normal(ks[1], (B, H, dk))
            for step in range(3):
                cache = AC.append(cache, kv, vv, cfg, seed=step)
            y = OPS.attn_decode(cache, q, cfg)
            results.append((backend, cache.k, y))
        else:
            pytest.skip(f"{kind}: single-backend kinds are not parity cases")
    (b0, S_ref, y_ref), rest = results[0], results[1:]
    for backend, Sn, y in rest:
        _assert_state_identical(S_ref, Sn, (kind, fmt, b0, backend))
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"{kind}/{fmt}: {b0} vs {backend}")
