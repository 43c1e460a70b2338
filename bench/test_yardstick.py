"""The benchmark's byte and operation counts against the program's
operator descriptors (``traffic(plan)``), at one shape per kind, and the
peaks table."""
import pytest

from bench import yardstick as Y

B, T = 2, 256


@pytest.fixture(scope="module")
def plans():
    from repro import ops as OPS
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("zamba2-2.7b")
    out = {}
    for e in OPS.decode_op_plans(cfg, B, T, layout="paged"):
        t = e.traffic
        out[e.kind] = (e.plan, (t.state_read + t.state_write + t.operand_read
                                + t.output_write) / e.count)
    return out


def test_state_update_bytes_match_the_descriptor(plans):
    plan, want = plans["state_update"]
    _, got = Y.state_update_call(B, plan.dim("H"), plan.dim("dk"),
                                 plan.dim("dv"), state_bytes_per_val=1.0,
                                 operand_bytes=2.0)
    assert got == want


def test_attn_decode_bytes_match_the_descriptor(plans):
    plan, want = plans["attn_decode"]
    assert plan.dim("dk") == plan.dim("dv")
    _, got = Y.attn_decode_call([T] * B, plan.dim("H"), plan.dim("KVH"),
                                plan.dim("dk"), kv_bytes_per_val=1.0,
                                operand_bytes=2.0)
    block_table = B * (T // Y.PAGE_TOKENS) * 4.0   # the descriptor's walk
    assert got == want - block_table


def test_kv_append_bytes_match_the_descriptor(plans):
    plan, want = plans["kv_append"]
    _, got = Y.kv_append_call(B, plan.dim("KVH"), plan.dim("dk"),
                              kv_bytes_per_val=1.0, operand_bytes=2.0)
    assert got == want - B * 4.0                   # the descriptor's slot id


def test_shared_pages_count_once():
    # two rows over the same two full pages and a private third
    rows = [(300, [5, 6, 7]), (256, [5, 6])]
    assert Y.kv_tokens(rows) == [300, 0]
    assert Y.kv_tokens([(130, [1, 2]), (130, [3, 4])]) == [130, 130]


def test_mx8_stored_size():
    assert Y.MX8_STORED_BYTES == 1.125    # mantissa + exponent/16 + micro/16


def test_peaks_by_device_kind():
    pk = Y.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        Y.peaks("cpu")


def test_weight_bytes_match_the_weights_made():
    import json
    import os
    from bench import model as BM
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("mamba2-2.7b",):
        with open(os.path.join(root, "bench", "configs", name + ".json")) as f:
            m = json.load(f)["model"]
        s = Y.shapes(m)
        # a tied head is the embedding table, which the count then includes
        embed = 0 if m.get("tie_embeddings") else 4 * s["V"] * s["d"]
        assert Y.weight_bytes(m) + embed == BM.weight_bytes(m)
