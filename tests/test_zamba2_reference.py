"""Zamba2's hybrid schedule against its plain reference.

At smoke size on the CPU, with weights from the reference's own maker
(``bench/configs/zamba2-2.7b.py``), the program's prefill and then its
paged decode steps, fed the same tokens, give the logits of the
reference's full forward pass (float32, the recurrence token by token,
attention over the whole causal score matrix).  The toy model keeps the
published structure: two shared blocks alternating over three
applications at irregular gaps, the embedding concatenated to the block
input, each application's LoRA and linear.
"""
import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.harness import program_config  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving.memory import PAGE_TOKENS, PagedStatePool, pages_for  # noqa: E402

MODEL = {
    "name": "zamba2-smoke", "family": "hybrid", "n_layers": 7,
    "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32,
    "d_ff": 128, "vocab_size": 512, "pattern": ["mamba2"],
    "ffn_kind": "none", "pos_emb": "none", "norm_eps": 1e-05,
    "tie_embeddings": True, "hybrid_layer_ids": [2, 4, 5],
    "n_mem_blocks": 2, "adapter_rank": 8,
    "ssm": {"d_state": 16, "head_dim": 16, "expand": 2, "d_conv": 4,
            "chunk": 16},
    "param_dtype": "float32", "compute_dtype": "float32"}

PROMPT, STEPS = 125, 6          # the decode steps cross a page boundary


def _reference():
    spec = importlib.util.spec_from_file_location(
        "zamba2_reference",
        os.path.join(ROOT, "bench", "configs", "zamba2-2.7b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# tolerances, relative L2 distance of a position's logits:
# * fp32 state and K/V: the same float32 arithmetic in another order (the
#   prefill's chunked scan and blockwise attention against a sequential
#   recurrence and one softmax); readings at most 4e-6, so 1e-4 leaves
#   room and a bf16 computation (about 1e-2) could not pass;
# * MX8 state and K/V with stochastic rounding: the prefill logits never
#   see the rounding (the caches are quantized after them, so 1e-4 holds),
#   and each decode step reads MX8 caches: readings 0.021-0.037 on three
#   seeds, so 0.1 leaves room for other seeds and draws.
CASES = [("fp32", "jnp", "nearest", 1e-4, 1e-4),
         ("mx8", "pallas", "stochastic", 1e-4, 0.1)]


@pytest.mark.parametrize("fmt,backend,rounding,tol_prefill,tol_decode",
                         CASES, ids=[c[0] for c in CASES])
def test_prefill_then_paged_decode_match_reference(fmt, backend, rounding,
                                                   tol_prefill, tol_decode):
    m = dict(MODEL, state_quant={"fmt": fmt, "rounding": rounding,
                                 "backend": backend})
    cfg = program_config(m)
    ref = _reference()
    w = ref.make_weights(m, 2 ** 31 + 3)
    toks = np.random.default_rng(5).integers(
        0, m["vocab_size"], PROMPT + STEPS).astype(np.int32)
    want = np.asarray(ref.reference_logits(w, toks, m))

    pool = PagedStatePool(cfg, n_pages=6, n_slabs=3)
    logits, row = M.prefill(w, cfg, {"tokens": jnp.asarray(toks[:PROMPT])[None]})
    assert _rel(logits[0], want[PROMPT - 1]) <= tol_prefill
    assert pool.register(1, pages_for(PROMPT))
    pool.insert_prefill(1, row)
    errs = []
    for i in range(STEPS):
        pos = PROMPT + i
        while pos // PAGE_TOKENS + 1 > len(pool.page_table[1]):
            assert pool.grow(1, 1)
        lg = pool.decode(w, [1, None], np.array([toks[pos], 0], np.int32),
                         np.array([pos, 0], np.int32), seed=i + 1)
        errs.append(_rel(lg[0], want[pos]))
    assert max(errs) <= tol_decode, errs
