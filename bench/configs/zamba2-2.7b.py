"""Plain reference and weights of Zamba2-2.7B: 54 Mamba-2 layers and two
shared attention + MLP blocks applied before nine of them.

Reading the equations (``modeling_zamba2.py`` of ``transformers``; the
Mamba-2 layer is ``bench/model.py``'s): x0 is the embedding, x the stream.
Before layer ``hybrid_layer_ids[j]``, block ``j % n_mem_blocks`` reads
h = RMSNorm(concat(x, x0)) (width 2d); q, k, v = h Wq, h Wk, h Wv in heads
of ``head_dim``; causal softmax attention with scores scaled by
(head_dim / 2) ** -0.5; o = attn Wo (back to d); g = RMSNorm(o);
[gate, up] = g Wi + (g A_j) B_j (application j's own rank-r adapter);
m = (gelu_erf(gate) * up) Wdown; t = m L_j (its own linear).  The block has
no residual inside.  Layer i then computes x += Mamba2(RMSNorm(x + t)) with
t = 0 at layers without an application: t reaches the layer's input, never
the stream.  Final RMSNorm, head tied to the embedding.

Everything runs in float32 at the ``highest`` matmul precision, the
recurrence token by token, attention over the whole causal score matrix.
Nothing here comes from the program under test.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import (_mamba2, _nest, _rmsnorm, _weight_specs, int4,
                         key_from_seed, rel_err)


def _shared_specs(m: dict):
    """(path, shape, init) of the shared blocks and of each application."""
    d, dff, r = m["d_model"], m["d_ff"], m["adapter_rank"]
    qd, kd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    out_scale = 1.0 / math.sqrt(2 * m["n_layers"])
    specs = []
    for b in range(m["n_mem_blocks"]):
        pre = ("shared", b)
        specs += [
            (pre + ("norm", "scale"), (2 * d,), ("const", 1.0)),
            (pre + ("attn", "wq"), (2 * d, qd), ("normal", 1 / math.sqrt(2 * d))),
            (pre + ("attn", "wk"), (2 * d, kd), ("normal", 1 / math.sqrt(2 * d))),
            (pre + ("attn", "wv"), (2 * d, kd), ("normal", 1 / math.sqrt(2 * d))),
            (pre + ("attn", "wo"), (qd, d), ("normal", out_scale / math.sqrt(qd))),
            (pre + ("ffn_norm", "scale"), (d,), ("const", 1.0)),
            (pre + ("ffn", "wi"), (d, 2 * dff), ("normal", 1 / math.sqrt(d))),
            (pre + ("ffn", "wo"), (dff, d), ("normal", out_scale / math.sqrt(dff))),
        ]
    for j in range(len(m["hybrid_layer_ids"])):
        pre = ("hybrid", j)
        specs += [
            (pre + ("linear",), (d, d), ("normal", 1 / math.sqrt(d))),
            (pre + ("lora_a",), (d, r), ("normal", 1 / math.sqrt(d))),
            (pre + ("lora_b",), (r, 2 * dff), ("normal", 1 / math.sqrt(r))),
        ]
    return specs


def weight_specs(m: dict):
    """Every weight: the Mamba-2 stack, embedding and final norm as
    ``bench/model.py`` lays them out, then the shared blocks."""
    return _weight_specs(m) + _shared_specs(m)


def make_weights(m: dict, seed: int):
    """Every weight, on the device, from the seed, in one jitted call, in the
    program's parameter tree."""
    specs = weight_specs(m)
    H = m["ssm"]["expand"] * m["d_model"] // m["ssm"]["head_dim"]

    def build(key):
        flat = {}
        for i, (path, shape, init) in enumerate(specs):
            if init[0] == "normal":
                a = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * init[1]
            elif init[0] == "const":
                a = jnp.full(shape, init[1], jnp.float32)
            elif init[0] == "alog":
                a = jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, H)),
                                     shape)
            else:                       # dt_bias: softplus^-1(0.01)
                a = jnp.full(shape, np.log(np.expm1(0.01)), jnp.float32)
            flat[path] = a.astype(jnp.float32)
        return _nest(flat)

    return jax.jit(build)(key_from_seed(seed))


def weight_bytes(m: dict) -> int:
    return sum(4 * int(np.prod(s)) for _, s, _ in weight_specs(m))


# ---------------------------------------------------------------------------
# the plain forward pass
# ---------------------------------------------------------------------------

def _attention(p, h, m: dict, kv_quant):
    """Causal softmax attention over the whole sequence: h (S, 2d)."""
    S = h.shape[0]
    H, KVH, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = (h @ p["wq"]).reshape(S, H, dh)
    k = (h @ p["wk"]).reshape(S, KVH, dh)
    v = (h @ p["wv"]).reshape(S, KVH, dh)
    if kv_quant is not None:
        k, v = kv_quant(k).astype(h.dtype), kv_quant(v).astype(h.dtype)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32)
    s = s * (dh / 2) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    return jnp.einsum("hqk,khd->qhd", a, v).reshape(S, H * dh) @ p["wo"]


def _application(p, a, x, x0, m: dict, eps, kv_quant):
    """One shared-block application: what it adds to its layer's input."""
    h = _rmsnorm(jnp.concatenate([x, x0], axis=-1), p["norm"]["scale"], eps)
    o = _attention(p["attn"], h, m, kv_quant)
    g = _rmsnorm(o, p["ffn_norm"]["scale"], eps)
    gu = g @ p["ffn"]["wi"] + (g @ a["lora_a"]) @ a["lora_b"]
    gate, up = jnp.split(gu, 2, axis=-1)
    y = (jax.nn.gelu(gate.astype(jnp.float32), approximate=False)
         .astype(g.dtype) * up) @ p["ffn"]["wo"]
    return y @ a["linear"]


#: control -> (compute type, matmul precision, rounding of the recurrent
#: state after every step and of K/V as they are cached).  The
#: configuration states float32 at the ``highest`` precision with MX8 state
#: and K/V: ``high`` is three bf16 passes on a TPU, ``bf16`` bfloat16
#: weights and activations, ``int4`` 4-bit state and K/V.
CONTROLS = {"high": (jnp.float32, "high", None),
            "bf16": (jnp.bfloat16, "highest", None),
            "int4": (jnp.float32, "highest", int4)}


def forward_pass(m: dict, control=None):
    """``(weights, tokens (S,)) -> logits (S, V)`` of the plain forward pass;
    with ``control`` (a key of ``CONTROLS``) the pass one precision lower.

    The pass runs from the host as jitted pieces -- the embedding, one
    Mamba-2 layer, one shared-block application, the head -- each layer's
    weights sliced off the stack first.  One program over every layer would
    let XLA convert the whole layer stack to a control's type at once,
    outside its loop: 5.2 GB of bf16, more than fits beside the weights."""
    dtype, precision, quant = CONTROLS.get(control,
                                           (jnp.float32, "highest", None))
    eps = m.get("norm_eps", 1e-5)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)

    def at_precision(f):
        def run(*args):
            with jax.default_matmul_precision(precision):
                return f(*args)
        return jax.jit(run)

    @at_precision
    def embed(table, tokens):
        return table[tokens].astype(dtype)

    @at_precision
    def layer(p, x, t):
        p = cast(p)
        return x + _mamba2(p["mixer"], _rmsnorm(x + t, p["norm"]["scale"], eps),
                           m, eps, quant)

    @at_precision
    def application(p, a, x, x0):
        return _application(cast(p), cast(a), x, x0, m, eps, quant)

    @at_precision
    def head(table, scale, x):
        x = _rmsnorm(x, scale.astype(dtype), eps)
        return (x @ table.T.astype(dtype)).astype(jnp.float32)

    def run(w, tokens):
        x0 = x = embed(w["embed"], tokens)
        layers = w["groups"][0]              # every Mamba-2 layer, stacked
        bounds = (0,) + tuple(m["hybrid_layer_ids"]) + (m["n_layers"],)
        for j, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
            for i in range(lo, hi):
                t = jnp.zeros_like(x)
                if i == lo and j:            # application j - 1, before lo
                    t = application(w["shared"][(j - 1) % m["n_mem_blocks"]],
                                    w["hybrid"][j - 1], x, x0)
                x = layer(jax.tree.map(lambda a: a[i], layers), x, t)
        return head(w["embed"], w["final_norm"]["scale"], x)
    return run


def reference_logits(w, tokens, m: dict, control=None):
    """Logits (S, V) of the plain forward pass over ``tokens`` (S,)."""
    return forward_pass(m, control)(w, tokens)


@jax.jit
def _widest(ref, mask, tokens):
    """The widest gap ``max(ref_i) - ref_i[token_i]`` over masked i."""
    gap = ref.max(-1) - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]
    return jnp.max(jnp.where(mask, gap, 0.0))


def _compare_fn(m: dict, controls: Sequence[str]):
    """As ``bench/model.make_compare_fn``, over this model's forward."""
    ref_pass = forward_pass(m)
    passes = {c: forward_pass(m, c) for c in controls}

    def fn(w, tokens, targets, mask, at):
        ref = ref_pass(w, tokens)
        ctl = {}
        for c, f in passes.items():
            lc = f(w, tokens)
            ctl[c] = (_widest(ref, mask, jnp.argmax(lc, -1)), lc[at])
        return ref[at], _widest(ref, mask, targets), ctl
    return fn


def served_readings(w, m: dict, requests, pad_to: int,
                    controls: Sequence[str] = ()
                    ) -> Tuple[Dict[str, Dict[str, float]], int]:
    """``prefill_err`` and ``max_gap`` of the program and of each control,
    read as ``bench/model.served_readings`` reads them."""
    fn = _compare_fn(m, tuple(controls))
    out = {s: {"prefill_err": 0.0, "max_gap": 0.0}
           for s in ("program",) + tuple(controls)}
    n = 0
    for prompt, served, s0, logits in requests:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        assert len(seq) <= pad_to, (len(seq), pad_to)
        tokens = np.zeros(pad_to, np.int32)
        tokens[:len(seq)] = seq
        targets = np.zeros(pad_to, np.int32)
        mask = np.zeros(pad_to, bool)
        p0 = len(prompt) - 1                 # logits here give served[0]
        targets[p0:p0 + len(served)] = served
        mask[p0:p0 + len(served)] = True
        ref_at, gap, ctl = fn(w, tokens, targets, mask, np.int32(s0 - 1))
        got = {"program": (gap, logits), **ctl}
        for side, (g, at) in got.items():
            r = out[side]
            r["max_gap"] = max(r["max_gap"], float(g))
            err = float("inf") if at is None else rel_err(at, ref_at)
            r["prefill_err"] = max(r["prefill_err"], err)
        n += len(served)
    return out, n
