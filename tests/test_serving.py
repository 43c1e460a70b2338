"""Serving engine integration: continuous batching correctness + throughput
accounting on a tiny model."""
import jax
import numpy as np
import pytest
from conftest import greedy_reference

from repro.configs import get_smoke_config
from repro.core.state_update import StateQuantConfig
from repro.models import model as M
from repro.serving.api import ServeConfig
from repro.serving.engine import PagedServingEngine, Request


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("llama3.2-1b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def test_engine_single_request(tiny):
    params, cfg = tiny
    eng = PagedServingEngine(params, cfg, ServeConfig(batch=2, n_pages=9))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    done = eng.run()
    assert len(done) == 1
    assert len(done[0].output) == 6
    assert all(0 <= t < cfg.vocab_size for t in done[0].output)


def test_engine_batched_requests_complete(tiny):
    params, cfg = tiny
    eng = PagedServingEngine(params, cfg, ServeConfig(batch=3, n_pages=9))
    rng = np.random.default_rng(1)
    n_req = 7   # > batch: exercises admission + row reuse
    for i in range(n_req):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               8 + i).astype(np.int32),
                           max_new_tokens=4 + (i % 3)))
    done = eng.run()
    assert len(done) == n_req
    for r in done:
        assert len(r.output) == 4 + (r.rid % 3)
    stats = eng.stats()
    assert stats["tokens"] == sum(4 + (i % 3) for i in range(n_req))
    assert stats["tokens_per_s"] > 0


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-2.7b",
                                  "zamba2-2.7b"])
def test_engine_matches_unbatched_greedy(arch):
    """Continuous batching must not change any request's greedy tokens.

    Note: the decode seed differs between engine steps and the reference
    loop, so run the quant-free config where SR seeds cannot matter."""
    cfg = get_smoke_config(arch).with_(
        state_quant=StateQuantConfig(fmt="fp32", rounding="nearest",
                                     backend="jnp"))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (10, 13, 9)]
    refs = [greedy_reference(params, cfg, p, 5) for p in prompts]

    eng = PagedServingEngine(params, cfg, ServeConfig(batch=3, n_pages=9))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    done = sorted(eng.run(), key=lambda r: r.rid)
    for r, ref_toks in zip(done, refs):
        assert r.output == ref_toks, (r.rid, r.output, ref_toks)


def test_engine_hybrid_model():
    cfg = get_smoke_config("zamba2-2.7b")
    params = M.init_model(jax.random.PRNGKey(3), cfg)
    eng = PagedServingEngine(params, cfg, ServeConfig(batch=2, n_pages=9))
    rng = np.random.default_rng(3)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6
                                                      ).astype(np.int32),
                           max_new_tokens=3))
    done = eng.run()
    assert len(done) == 3
