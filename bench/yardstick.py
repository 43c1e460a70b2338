"""The benchmark's own arithmetic: peaks, operations and bytes, percentiles.

Everything here is computed from shapes and sizes the benchmark knows, so
"the same work" reads the same whatever implements it.  The byte counts are
what the work must move at the least:

* weights are read once per step (of the embedding table only the rows the
  step looks up);
* a recurrent state is read and written once per row and layer at its
  stored MX8 size (``MX8_STORED_BYTES`` per value: one mantissa byte, and
  one exponent byte and one micro-exponent byte per 16 values);
* attention reads each row's K/V up to its length, and a physical page that
  several rows read in one step counts once; the appended token is written.

The arithmetic of the state update and of paged attention is copied from
the program's operator descriptors (``traffic(plan)`` of ``state_update``,
``attn_decode`` and ``kv_append``); ``test_yardstick.py`` checks the copy
against them at one shape per kind.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, Iterable, List, Sequence, Tuple

PAGE_TOKENS = 128
MX8_STORED_BYTES = 1.0 + 2.0 / 16.0
F32 = 4.0

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s and HBM bytes/s of one chip; an unknown device is an
    error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def floor_seconds(flops: float, nbytes: float, pk: Dict[str, float]
                  ) -> float:
    """The least time the chip could take: operations or bytes bound it."""
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# model shapes
# ---------------------------------------------------------------------------

def shapes(m: dict) -> Dict[str, int]:
    d = m["d_model"]
    di = m["ssm"]["expand"] * d
    return {"d": d, "di": di, "H": di // m["ssm"]["head_dim"],
            "N": m["ssm"]["d_state"], "P": m["ssm"]["head_dim"],
            "dc": m["ssm"]["d_conv"], "V": m["vocab_size"],
            "L": m["n_layers"], "G": m["n_layers"] // len(m["pattern"]),
            "AH": m["n_heads"], "AKV": m["n_kv_heads"], "dh": m["head_dim"],
            "dff": m["d_ff"], "shared": int(bool(m.get("shared_attn")))}


def _matrices(m: dict) -> Tuple[int, int, int]:
    """(weights of one Mamba-2 layer's matrices, of the shared block's
    matrices, of the head)."""
    s = shapes(m)
    d, di, H, N = s["d"], s["di"], s["H"], s["N"]
    mamba = d * di * 2 + d * 2 * N + d * H + di * d
    shared = 0
    if s["shared"]:
        qd, kd = s["AH"] * s["dh"], s["AKV"] * s["dh"]
        shared = d * qd + 2 * d * kd + qd * d + 3 * d * s["dff"]
    return mamba, shared, d * s["V"]


def matmul_params(m: dict) -> Tuple[int, int]:
    """(weights every token multiplies by, the head's): the shared block
    counts once for each of its G applications."""
    s = shapes(m)
    mamba, shared, head = _matrices(m)
    return s["L"] * mamba + s["G"] * shared, head


def weight_bytes(m: dict) -> float:
    """Bytes of every stored f32 weight except the embedding table (the
    shared block is stored once; a head tied to the embedding is counted
    as the head)."""
    s = shapes(m)
    mamba, shared, head = _matrices(m)
    small = s["L"] * (s["d"] + s["dc"] * (s["di"] + 2 * s["N"])
                      + s["di"] + 2 * s["N"] + 3 * s["H"] + s["di"])
    if s["shared"]:
        small += 2 * s["d"]
    return F32 * (s["L"] * mamba + shared + head + small + s["d"])


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def state_update_call(rows: int, H: int, dk: int, dv: int,
                      state_bytes_per_val: float = MX8_STORED_BYTES,
                      operand_bytes: float = F32) -> Tuple[float, float]:
    """(flops, bytes) of one state update over ``rows`` rows:
    S' = d S + k v^T (3 flops a value), y = S'^T q (2 flops a value)."""
    vals = rows * H * dk * dv
    state = 2 * vals * state_bytes_per_val               # read + write
    operands = rows * H * (3 * dk + dv) * operand_bytes  # d, k, q and v
    out = rows * H * dv * F32
    return 5.0 * vals, state + operands + out


def kv_tokens(rows: Iterable[Tuple[int, Sequence[int]]]) -> List[int]:
    """Tokens of K/V each row must read, a shared physical page once.

    ``rows``: (context length incl. the appended token, page ids).  A page
    read by several rows counts once, at the most tokens any of them
    reads from it."""
    seen: Dict[int, int] = {}
    out = []
    for length, pages in rows:
        n = 0
        for j, pid in enumerate(pages[:-(-length // PAGE_TOKENS)]):
            toks = min(PAGE_TOKENS, length - j * PAGE_TOKENS)
            extra = max(0, toks - seen.get(pid, 0))
            seen[pid] = max(seen.get(pid, 0), toks)
            n += extra
        out.append(n)
    return out


def attn_decode_call(tokens: Sequence[int], AH: int, AKV: int, dh: int,
                     kv_bytes_per_val: float = MX8_STORED_BYTES,
                     operand_bytes: float = F32) -> Tuple[float, float]:
    """(flops, bytes) of one decode attention over rows reading ``tokens``
    K/V positions each: q.K and p.V are 4 flops per position and head dim."""
    flops = sum(4.0 * t * AH * dh for t in tokens)
    kv = sum(t * 2 * AKV * dh * kv_bytes_per_val for t in tokens)
    qo = len(tokens) * AH * dh * (operand_bytes + F32)
    return flops, kv + qo


def kv_append_call(rows: int, AKV: int, dh: int,
                   kv_bytes_per_val: float = MX8_STORED_BYTES,
                   operand_bytes: float = F32) -> Tuple[float, float]:
    vals = rows * 2 * AKV * dh
    return 0.0, vals * (kv_bytes_per_val + operand_bytes)


# ---------------------------------------------------------------------------
# whole steps
# ---------------------------------------------------------------------------

def decode_step(m: dict, rows: Sequence[Tuple[int, Sequence[int]]]
                ) -> Tuple[float, float]:
    """(flops, bytes) of one decode step over the live ``rows``, each
    (context length incl. the appended token, page ids)."""
    s = shapes(m)
    B = len(rows)
    per_token, head = matmul_params(m)
    flops = 2.0 * B * (per_token + head)
    nbytes = weight_bytes(m) + B * s["d"] * F32          # + embedding rows
    channels = s["di"] + 2 * s["N"]
    flops += s["L"] * B * 2.0 * s["dc"] * channels       # the convolutions
    nbytes += s["L"] * B * 2 * (s["dc"] - 1) * channels * F32   # their tails
    f, b = state_update_call(B, s["H"], s["N"], s["P"])
    flops, nbytes = flops + s["L"] * f, nbytes + s["L"] * b
    if s["shared"]:
        f, b = attn_decode_call(kv_tokens(rows), s["AH"], s["AKV"], s["dh"])
        fa, ba = kv_append_call(B, s["AKV"], s["dh"])
        flops += s["G"] * (f + fa)
        nbytes += s["G"] * (b + ba)
    return flops, nbytes


def prefill(m: dict, S: int) -> Tuple[float, float]:
    """(flops, bytes) of one B=1 prefill of ``S`` tokens: matmuls for every
    token, the head for the last, causal attention, the recurrence."""
    s = shapes(m)
    per_token, head = matmul_params(m)
    flops = 2.0 * S * per_token + 2.0 * head
    flops += s["L"] * S * 5.0 * s["H"] * s["N"] * s["P"]
    flops += s["L"] * S * 2.0 * s["dc"] * (s["di"] + 2 * s["N"])
    if s["shared"]:
        flops += s["G"] * 2.0 * S * (S + 1) * s["AH"] * s["dh"]
    state = s["L"] * s["H"] * s["N"] * s["P"] * MX8_STORED_BYTES
    kv = s["shared"] * s["G"] * S * 2 * s["AKV"] * s["dh"] * MX8_STORED_BYTES
    return flops, weight_bytes(m) + S * s["d"] * F32 + state + kv
