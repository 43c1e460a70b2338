"""Prefill of a padded prompt with its padding masked (``M.prefill`` with
``lengths``) against the prefill of the exact tokens.

Tiny float32 configurations: a Mamba-2 stack and the Zamba2 hybrid (shared
attention blocks over Mamba-2 layers).  For each real length the padded
prefill must give the same last-position logits, recurrent state, conv
windows and K/V of the real positions, and a K/V length equal to the real
length; one padded length must compile once, whatever the real lengths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import attention_cache as AC
from repro.core import formats as F
from repro.core.state_update import StateQuantConfig
from repro.models import model as M

ARCHS = ("mamba2-2.7b", "zamba2-2.7b")
BUCKET = 48                 # the padded length: three scan chunks of 16


def _lengths(cfg):
    chunk, d_conv = cfg.ssm.chunk, cfg.ssm.d_conv
    return (1, 2, d_conv - 1, chunk, chunk + 1, BUCKET - 1)


@pytest.fixture(scope="module", params=ARCHS)
def model(request):
    cfg = get_smoke_config(request.param).with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    traces = []

    def prefill(params, batch, lengths=None):
        traces.append(batch["tokens"].shape)
        return M.prefill(params, cfg, batch, lengths=lengths)

    return cfg, params, jax.jit(prefill), traces


def _tokens(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _padded(prefill, params, toks, n):
    """The prefill of ``toks[:n]`` padded to BUCKET, the padding masked."""
    pad = jnp.asarray(np.pad(toks[:n], (0, BUCKET - n)))[None]
    return prefill(params, {"tokens": pad}, jnp.array([n], jnp.int32))


def _f32(t):
    return F.dequantize(t) if isinstance(t, F.QuantizedTensor) else t


def _split(caches):
    """(K/V caches, every other cache leaf as float32)."""
    leaves = jax.tree.leaves(
        caches, is_leaf=lambda t: isinstance(t, (AC.KVCache,
                                                 F.QuantizedTensor)))
    kv = [c for c in leaves if isinstance(c, AC.KVCache)]
    rest = [_f32(c) for c in leaves if not isinstance(c, AC.KVCache)]
    return kv, rest


def _close(a, b):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("i", range(6))
def test_padded_prefill_matches_exact(model, i):
    cfg, params, prefill, _ = model
    n = _lengths(cfg)[i]
    toks = _tokens(cfg, BUCKET, seed=n)
    exact_logits, exact = prefill(params,
                                  {"tokens": jnp.asarray(toks[:n])[None]})
    logits, padded = _padded(prefill, params, toks, n)
    _close(logits, exact_logits)

    kv_e, rest_e = _split(exact)
    kv_p, rest_p = _split(padded)
    # the recurrent state S and the conv windows conv_x, conv_bc
    assert len(rest_p) == len(rest_e) == 3
    for a, b in zip(rest_p, rest_e):
        assert a.shape == b.shape
        _close(a, b)
    assert len(kv_p) == len(kv_e) == (1 if cfg.shared_attn else 0)
    for cp, ce in zip(kv_p, kv_e):
        # stacked by application: (apps, B, T, KVH, dh)
        assert np.asarray(cp.lengths).tolist() == \
            np.asarray(ce.lengths).tolist() == [[n]] * cfg.n_shared_apps
        for tp, te in ((cp.k, ce.k), (cp.v, ce.v)):
            tp, te = _f32(tp), _f32(te)
            _close(tp[:, :, :n], te[:, :, :n])
            assert not np.asarray(tp[:, :, n:]).any()   # padding zeroed


def test_one_compile_per_padded_length(model):
    cfg, params, prefill, traces = model
    toks = _tokens(cfg, BUCKET)
    _padded(prefill, params, toks, 5)        # compiles the bucket (or not)
    before = len(traces)
    for n in (3, 17, 30, BUCKET):
        _padded(prefill, params, toks, n)
    assert len(traces) == before             # no further trace


def test_lengths_refused_where_padding_cannot_be_masked():
    cfg = get_smoke_config("gla-2.7b")
    assert M.masked_prefill_ok(get_smoke_config("zamba2-2.7b"))
    assert M.masked_prefill_ok(get_smoke_config("llama3.2-1b"))
    assert not M.masked_prefill_ok(cfg)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="cannot mask"):
        M.prefill(params, cfg, {"tokens": jnp.zeros((1, 8), jnp.int32)},
                  lengths=jnp.array([4], jnp.int32))
