"""The ``zamba2-chat-open`` cell at smoke size on the CPU: the same harness
code, traffic shape and reference (``bench/configs/zamba2-2.7b.py``) with a
toy model of the published structure -- two shared blocks alternating over
three applications at irregular layer gaps, the embedding concatenated to
the block input, each application's LoRA and linear.

``CHECK`` is this size's limit, set from CPU readings on four seeds: the
program's ``prefill_err`` at most 3.1e-6 (limit 1e-4), the bf16 control's
at least 0.061 and the int4 control's (state and K/V at 4 bits) at least
0.46; ``max_gap`` at most 0.022 for the program (limit 0.1; MX8 K/V moves
the served tokens here more than an MX8 state alone does), at least 0.18
for int4.  A planted structural fault fails it: one block for every
application, or a block that reads no embedding."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import yardstick_zamba2 as Z
from bench.yardstick import PAGE_TOKENS

MODEL = {
    "name": "zamba2-smoke", "family": "hybrid", "n_layers": 7,
    "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 32,
    "d_ff": 128, "vocab_size": 512, "pattern": ["mamba2"],
    "ffn_kind": "none", "pos_emb": "none", "norm_eps": 1e-05,
    "tie_embeddings": True, "hybrid_layer_ids": [2, 4, 5],
    "n_mem_blocks": 2, "adapter_rank": 8,
    "ssm": {"d_state": 16, "head_dim": 16, "expand": 2, "d_conv": 4,
            "chunk": 16},
    "state_quant": {"fmt": "mx8", "rounding": "stochastic",
                    "backend": "pallas"},
    "param_dtype": "float32", "compute_dtype": "float32"}

CHECK = {"prefill_err": 1e-4, "max_gap": 0.1, "sample": 64}

MIX = {"rate_rps": 4.0, "lead_s": 1.0, "trace_s": 2.0,
       "prompt": {"median": 40, "sigma": 0.5, "min": 16, "max": 64},
       "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
       "pool": {"batch": 4, "n_pages": 17, "n_slabs": 5, "prefill_chunk": 64,
                "prefill_buckets": [16, 32, 64]}}


def run(seed: int = 7, seconds: float = 3.0, **kw) -> dict:
    import time
    return harness.run_cell(
        "zamba2-chat-open", seed, seconds, kw.pop("trace", False),
        time.perf_counter(), allow_cpu=True,
        config_override={"model": MODEL, "check": CHECK}, mix_override=MIX,
        **kw)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_program_correct_controls_not(seed):
    out = run(seed=seed, controls=("bf16", "int4"))
    assert out["check"]["compared_tokens"]["value"] > 0
    assert out["correct"] is True and out["failed"] == 0, out["check"]
    for c in ("bf16", "int4"):
        assert out["controls"][c]["correct"] is False, out["controls"][c]
    assert list(out)[-1] == "check"


def _one_block(monkeypatch):
    from repro.models import model as M
    monkeypatch.setattr(M, "_block_of", lambda cfg, app: 0)


def _no_embedding(monkeypatch):
    from repro.models import model as M
    real = M._shared_in
    monkeypatch.setattr(M, "_shared_in",
                        lambda p, cfg, x, x0: real(p, cfg, x,
                                                   jnp.zeros_like(x0)))


@pytest.mark.parametrize("plant", [_one_block, _no_embedding],
                         ids=["one-block", "no-embedding"])
def test_planted_structure_fault_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    out = run(seed=2 ** 31 + 11)
    assert out["check"]["compared_tokens"]["value"] > 0
    assert out["correct"] is False, out["check"]


def test_traced_run_reads_the_span_metrics():
    """On the CPU no device plane exists: the device-trace readers find
    nothing, and the program's ``serve.step`` args give ``kv_tokens``."""
    out = run(trace=True)
    spec = harness.load_spec()
    layer = {m["name"]: m for m in spec["per_layer"]
             if "zamba2-chat-open" in m["workloads"]}
    assert out["correct"] is True
    assert set(out["metrics"]) <= set(layer)
    assert all(layer[n]["source"] != "device_trace" for n in out["metrics"])
    assert out["metrics"]["kv_tokens_step.zchat"]["value"] > 0


def test_cell_files_are_found_by_name():
    rs = harness.resolve(harness.load_spec(), "zamba2-chat-open")
    assert rs["config"]["reduced"] == [] and rs["cell"]["chips"] == 1
    assert {m["name"] for m in rs["end_to_end"]} == {
        "ttft_p90_s", "itl_p99_ms", "setup_s"}
    names = {m["name"] for m in rs["per_layer"]}
    assert {"decode_mfu.zchat", "prefill_mfu.zchat",
            "attn_decode_roofline.zchat", "kv_tokens_step.zchat"} <= names
    assert not names & {"decode_mfu.chat", "prefill_mfu.chat"}
    cfg = harness.program_config(rs["config"]["model"])
    assert cfg.hybrid_layer_ids == (6, 12, 18, 24, 30, 36, 42, 47, 51)
    hash(cfg)


def _published():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "zamba2-2.7b.json")) as f:
        return json.load(f)["model"]


def test_counts_at_the_published_widths():
    """2.662 B parameters; a shared block is 682 MB of f32 and a step
    reads it once per application."""
    m = _published()
    rs = harness.resolve(harness.load_spec(), "zamba2-chat-open")
    # the head is tied: the embedding table is the head
    assert Z.stored_bytes(m) == rs["reference"].weight_bytes(m)
    assert 2.66e9 < Z.stored_bytes(m) / 4 < 2.665e9
    mamba, block, app, head = Z._matrices(m)
    assert block * 4 == pytest.approx(682e6, rel=1e-3)
    extra = Z.step_weight_bytes(m) - Z.stored_bytes(m)
    assert extra == pytest.approx(7 * (block + 3 * 2560) * 4)


def test_attention_counts_match_the_descriptor():
    """``attn_decode_step`` is the yardstick's paged attention call once
    per application, at the program's descriptor bytes."""
    from repro import ops as OPS
    from repro.configs import get_config
    cfg = get_config("zamba2-2.7b")
    B, T = 2, 256
    plans = {e.kind: e for e in OPS.decode_op_plans(cfg, B, T,
                                                    layout="paged")}
    e = plans["attn_decode"]
    assert e.count == 9 == cfg.n_shared_apps
    t = e.traffic
    want = (t.state_read + t.state_write + t.operand_read
            + t.output_write) / e.count
    _, got = Z.attn_decode_call([T] * B, e.plan.dim("H"), e.plan.dim("KVH"),
                                e.plan.dim("dk"), kv_bytes_per_val=1.0,
                                operand_bytes=2.0)
    assert got == want - B * (T // PAGE_TOKENS) * 4.0   # the table walk
    rows = [(T, [1, 2]), (T, [3, 4])]
    f, b = Z.attn_decode_step(_published(), rows)
    f1, b1 = Z.attn_decode_call([T, T], 32, 32, 160)
    assert (f, b) == (9 * f1, 9 * b1)
    assert np.isfinite(Z.decode_step(_published(), rows)).all()
