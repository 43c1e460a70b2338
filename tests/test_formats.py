"""Unit + property tests for the low-precision formats (paper §3.2/§5.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# optional in the execution environment; CI installs it (see ci.yml).
# importorskip keeps the module COLLECTABLE either way -- a module-level
# ImportError would abort the whole suite's collection, not just this file.
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import formats as F

FMTS = ["mx8", "int8", "fp8_e4m3", "fp8_e5m2", "fp16"]


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("shape", [(4, 32), (2, 3, 128), (1, 256)])
def test_roundtrip_shapes(fmt, shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    qt = F.quantize(x, fmt)
    xd = F.dequantize(qt)
    assert xd.shape == shape
    assert jnp.all(jnp.isfinite(xd))


def test_error_ordering_matches_paper():
    """Fig. 6 accuracy axis: int8 < mx8 < e4m3 < e5m2 in RMS error for
    well-scaled data (mantissa width ordering)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 256))
    rms = {f: float(jnp.sqrt(jnp.mean((F.dequantize(F.quantize(x, f)) - x) ** 2)))
           for f in ["int8", "mx8", "fp8_e4m3", "fp8_e5m2"]}
    assert rms["int8"] < rms["mx8"] < rms["fp8_e4m3"] < rms["fp8_e5m2"]


def test_mx8_idempotent():
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 128))
    xd = F.dequantize(F.mx8_quantize(x))
    xd2 = F.dequantize(F.mx8_quantize(xd))
    assert jnp.array_equal(xd, xd2)


def test_mx8_zero_group():
    x = jnp.zeros((2, 32))
    qt = F.mx8_quantize(x)
    assert float(jnp.abs(F.dequantize(qt)).sum()) == 0.0


def test_mx8_storage_budget():
    """MX8 must average exactly 8 bits/value: 7 payload + 8/16 exp + 1/2 µe."""
    assert F.FORMAT_BITS["mx8"] == 8.0
    qt = F.mx8_quantize(jnp.ones((4, 64)))
    n = 4 * 64
    logical_bits = (qt.payload["mantissa"].size * 7
                    + qt.payload["exponent"].size * 8
                    + qt.payload["micro"].size * 8)
    assert logical_bits == n * 8


def test_sr_unbiased():
    """Stochastic rounding preserves values in expectation (the property that
    defeats swamping, paper §3.2)."""
    val = 0.031415  # not representable in 6-bit mantissa
    x = jnp.full((4096, 16), val)
    bits = F.sr_bits(x.shape, seed=7)
    got = float(F.dequantize(F.mx8_quantize(x, "stochastic", bits)).mean())
    # nearest rounding collapses to the representable neighbor; SR's sample
    # mean must beat RNE's systematic bias by a wide margin
    rne = float(F.dequantize(F.mx8_quantize(x, "nearest")).mean())
    assert abs(got - val) < abs(rne - val) / 5
    assert abs(rne - val) > 1e-4


def test_counter_hash_deterministic_and_uniform():
    b1 = F.sr_bits((1000,), seed=3)
    b2 = F.sr_bits((1000,), seed=3)
    assert jnp.array_equal(b1, b2)
    b3 = F.sr_bits((1000,), seed=4)
    assert not jnp.array_equal(b1, b3)
    u = np.asarray(b1, dtype=np.float64) / 2**32
    assert 0.4 < u.mean() < 0.6
    assert abs(np.mean(u < 0.25) - 0.25) < 0.05


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(min_value=-1e4, max_value=1e4,
                          allow_nan=False, allow_infinity=False),
                min_size=16, max_size=16))
def test_mx8_error_bound_property(vals):
    """|x - q(x)| <= 2^-6 * group_max + tiny, for every element."""
    x = jnp.asarray(vals, jnp.float32)[None, :]
    xd = F.dequantize(F.mx8_quantize(x))
    gmax = float(jnp.max(jnp.abs(x)))
    err = float(jnp.max(jnp.abs(xd - x)))
    assert err <= gmax * 2.0 ** -5 + 1e-6


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_fp8_sr_stays_in_range(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed % 1000), (4, 32)) * 100
    bits = F.sr_bits(x.shape, seed=seed)
    for fmt in ("fp8_e4m3", "fp8_e5m2"):
        xd = F.dequantize(F.quantize(x, fmt, "stochastic", bits))
        assert jnp.all(jnp.isfinite(xd))
        assert float(jnp.max(jnp.abs(xd))) <= F._FP8_MAX[fmt]


def test_strict_mx_arith_close_to_fused():
    """The hardware MX-adder path (strict) vs our fused f32 path differ by
    at most one extra rounding step (DESIGN.md §2)."""
    key = jax.random.PRNGKey(5)
    a = jax.random.normal(key, (8, 64))
    b = jax.random.normal(jax.random.PRNGKey(6), (8, 64))
    strict = F.strict_mx_add(a, b)
    fused = F.dequantize(F.mx8_quantize(a + b))
    denom = jnp.maximum(jnp.abs(a + b), 1e-3)
    assert float(jnp.median(jnp.abs(strict - fused) / denom)) < 0.05


@pytest.mark.parametrize("heads,width", [(2, 16), (32, 160)])
def test_mx8_dequantize_rows_matches_transposed_payload(heads, width):
    """Rows-down dequantization (the paged K/V pools' layout: every head's
    values of a token in one column) gives the values of the lane-wise
    dequantization, bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(heads), (128, heads, width))
    qt = F.quantize(x * 10.0 ** jnp.arange(-2, 2, 4 / heads)[:, None],
                    "mx8")
    cols = {f: a.reshape(128, -1).T for f, a in qt.payload.items()}
    got = F.mx8_dequantize_rows(cols["mantissa"], cols["exponent"],
                                cols["micro"])
    want = F.dequantize(qt).reshape(128, -1).T
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
