"""The benchmark's operation and byte counts for the hybrid schedule
(Zamba2): Mamba-2 layers, and before some of them an application of one of
the shared attention + MLP blocks.

Built on ``bench/yardstick.py``'s primitives: the state update, paged
attention and its append at their MX8 stored sizes, ``kv_tokens`` (a shared
page once).  What differs from ``yardstick.decode_step`` (which counts one
block after every pattern group, at the stream's width):

* a block reads the stream concatenated with the embedding: its q, k, v
  projections are 2 d_model wide;
* its MLP has a fused gate/up projection, and each application adds its own
  rank-r adapter on it and its own d x d linear on the output;
* a step reads a shared block's weights once per application: one block is
  hundreds of MB at the published widths, so nothing keeps it on the chip
  between two of its applications.

The readers pass this module as ``ctx.yardstick`` to the shared readers of
``bench/readers.py`` and ``bench/metrics``, which call ``decode_step``,
``prefill`` and ``floor_seconds`` on it.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

# floor_seconds: the shared readers call it on this module
from bench.yardstick import (F32, MX8_STORED_BYTES, attn_decode_call,  # noqa: F401
                             floor_seconds, kv_append_call, kv_tokens,
                             state_update_call)


def shapes(m: dict) -> Dict[str, int]:
    d = m["d_model"]
    di = m["ssm"]["expand"] * d
    return {"d": d, "di": di, "H": di // m["ssm"]["head_dim"],
            "N": m["ssm"]["d_state"], "P": m["ssm"]["head_dim"],
            "dc": m["ssm"]["d_conv"], "V": m["vocab_size"],
            "L": m["n_layers"], "AH": m["n_heads"], "AKV": m["n_kv_heads"],
            "dh": m["head_dim"], "dff": m["d_ff"], "r": m["adapter_rank"],
            "A": len(m["hybrid_layer_ids"]), "nb": m["n_mem_blocks"]}


def _matrices(m: dict) -> Tuple[int, int, int, int]:
    """Weights of (one Mamba-2 layer's matrices, one shared block's, one
    application's own, the head)."""
    s = shapes(m)
    d, di, H, N = s["d"], s["di"], s["H"], s["N"]
    qd, kd = s["AH"] * s["dh"], s["AKV"] * s["dh"]
    mamba = d * di * 2 + d * 2 * N + d * H + di * d
    block = 2 * d * (qd + 2 * kd) + qd * d + 3 * d * s["dff"]
    app = d * d + s["r"] * (d + 2 * s["dff"])
    return mamba, block, app, d * s["V"]


def matmul_params(m: dict) -> Tuple[int, int]:
    """(weights every token multiplies by, the head's): a shared block
    counts once for each of its applications."""
    s = shapes(m)
    mamba, block, app, head = _matrices(m)
    return s["L"] * mamba + s["A"] * (block + app), head


def _small(m: dict) -> Tuple[int, int]:
    """Vector weights of (one Mamba-2 layer, one shared block)."""
    s = shapes(m)
    layer = (s["d"] + s["dc"] * (s["di"] + 2 * s["N"]) + s["di"]
             + 2 * s["N"] + 3 * s["H"] + s["di"])
    return layer, 3 * s["d"]


def stored_bytes(m: dict) -> float:
    """Bytes of every stored f32 weight except the embedding table, each
    shared block once (a head tied to the embedding is counted as the
    head)."""
    s = shapes(m)
    mamba, block, app, head = _matrices(m)
    layer, block_small = _small(m)
    return F32 * (s["L"] * (mamba + layer) + s["nb"] * (block + block_small)
                  + s["A"] * app + head + s["d"])


def step_weight_bytes(m: dict) -> float:
    """Weight bytes one forward pass reads: the stored weights, and each
    shared block again for every application past its first."""
    s = shapes(m)
    _, block, _, _ = _matrices(m)
    _, block_small = _small(m)
    return stored_bytes(m) + F32 * (s["A"] - s["nb"]) * (block + block_small)


def attn_decode_step(m: dict, rows: Sequence[Tuple[int, Sequence[int]]]
                     ) -> Tuple[float, float]:
    """(flops, bytes) of every application's ``spu_attn_decode`` in one
    decode step over the live ``rows``."""
    s = shapes(m)
    f, b = attn_decode_call(kv_tokens(rows), s["AH"], s["AKV"], s["dh"])
    return s["A"] * f, s["A"] * b


def decode_step(m: dict, rows: Sequence[Tuple[int, Sequence[int]]]
                ) -> Tuple[float, float]:
    """(flops, bytes) of one decode step over the live ``rows``, each
    (context length incl. the appended token, page ids)."""
    s = shapes(m)
    B = len(rows)
    per_token, head = matmul_params(m)
    flops = 2.0 * B * (per_token + head)
    nbytes = step_weight_bytes(m) + B * s["d"] * F32     # + embedding rows
    channels = s["di"] + 2 * s["N"]
    flops += s["L"] * B * 2.0 * s["dc"] * channels       # the convolutions
    nbytes += s["L"] * B * 2 * (s["dc"] - 1) * channels * F32   # their tails
    f, b = state_update_call(B, s["H"], s["N"], s["P"])
    flops, nbytes = flops + s["L"] * f, nbytes + s["L"] * b
    f, b = attn_decode_step(m, rows)
    fa, ba = kv_append_call(B, s["AKV"], s["dh"])
    return flops + f + s["A"] * fa, nbytes + b + s["A"] * ba


def prefill(m: dict, S: int) -> Tuple[float, float]:
    """(flops, bytes) of one B=1 prefill of ``S`` tokens: matmuls for every
    token, the head for the last, causal attention in every application,
    the recurrence."""
    s = shapes(m)
    per_token, head = matmul_params(m)
    flops = 2.0 * S * per_token + 2.0 * head
    flops += s["L"] * S * 5.0 * s["H"] * s["N"] * s["P"]
    flops += s["L"] * S * 2.0 * s["dc"] * (s["di"] + 2 * s["N"])
    flops += s["A"] * 2.0 * S * (S + 1) * s["AH"] * s["dh"]
    state = s["L"] * s["H"] * s["N"] * s["P"] * MX8_STORED_BYTES
    kv = s["A"] * S * 2 * s["AKV"] * s["dh"] * MX8_STORED_BYTES
    return flops, step_weight_bytes(m) + S * s["d"] * F32 + state + kv
