import os

# Smoke tests and benches must see the single real CPU device; only the
# dry-run module requests 512 placeholder devices (and only in its own
# process).  Tests that need a small multi-device mesh spawn subprocesses
# (see test_sharding.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Tier-1 runs with the shadow-ledger sanitizer on: every refcount transition
# in the paged/tiered pools is mirrored and double-free / use-after-evict /
# teardown-leak raise immediately (repro.analysis.lint.runtime).  Opt out of
# an individual run with REPRO_SANITIZE=0.
os.environ.setdefault("REPRO_SANITIZE", "1")

import numpy as np
import pytest

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")


def subprocess_env(**extra):
    """Env for test subprocesses: absolute src prepended to the INHERITED
    PYTHONPATH (never clobbered -- pytest may run from outside the repo)."""
    env = dict(os.environ)
    inherited = env.get("PYTHONPATH")
    env["PYTHONPATH"] = _SRC + (os.pathsep + inherited if inherited else "")
    env.update(extra)
    return env


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def greedy_reference(params, cfg, prompt, n_new):
    """One request's greedy tokens with no engine: the dense ``M.prefill``
    + ``M.decode_step`` loop, its caches sized for the whole continuation.
    The serving engine must reproduce them under any batching (fp32 state,
    where stochastic-rounding seeds cannot matter)."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import attention_cache as AC
    from repro.models import model as M

    S = len(prompt)
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    logits, caches = jax.jit(functools.partial(M.prefill, cfg=cfg))(
        params, batch={"tokens": tokens, "targets": tokens})
    caches = AC.recapacity(caches, -(-(S + n_new) // 128) * 128)
    lengths = jnp.full((1,), S, jnp.int32)
    caches = M.set_cache_lengths(caches, lengths)
    decode = jax.jit(functools.partial(M.decode_step, cfg=cfg))
    out = [int(jnp.argmax(logits[0]))]
    for step in range(n_new - 1):
        tok = jnp.asarray([out[-1]], jnp.int32)
        logits, caches = decode(params, tokens=tok, caches=caches,
                                lengths=lengths + step, seed=step + 1)
        out.append(int(jnp.argmax(logits[0])))
    return out


#: suffix of a test architecture name that asks for :func:`cell_state_config`
CELL = "@cell"


def cell_state_config(arch, backend="pallas"):
    """``arch``'s smoke model with the recurrent state of its chat cell: 80
    Mamba-2 heads of 64 at the published ``d_state`` (MX8, stochastic
    rounding), reached from the smoke width by a wider in-projection.
    Depth, width and vocabulary stay cut for the CPU."""
    import dataclasses

    from repro.configs import get_config, get_smoke_config
    from repro.core.state_update import StateQuantConfig

    cfg = get_smoke_config(arch)
    ssm = dataclasses.replace(cfg.ssm, d_state=get_config(arch).ssm.d_state,
                              head_dim=64, expand=80 * 64 // cfg.d_model)
    return cfg.with_(ssm=ssm, state_quant=StateQuantConfig(
        fmt="mx8", rounding="stochastic", backend=backend))
