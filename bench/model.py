"""Weights from the seed and the plain reference forward pass.

The benchmark's configurations are stacks of Mamba-2 layers (Mamba-2
2.7B).  This module knows that family and nothing of the program under
test: it imports nothing from ``repro``.

* :func:`make_weights` builds every weight on the device in one jitted call
  from the seed, in the type the program serves (float32), laid out as the
  program's parameter tree expects them (no separate head when the
  configuration ties it to the embedding).
* :func:`reference_logits` is the plain forward pass: float32 at the
  ``highest`` matmul precision, a sequential recurrence for every Mamba-2
  layer (no chunking), no quantized state.  The controls run the same pass
  one precision below what the configuration states (``CONTROLS``).
* :func:`served_readings` reads the numbers that decide ``correct``.

Reading the equations: a Mamba-2 layer (arXiv:2405.21060) on h = RMSNorm(x):
z = h Wz, u = h Wx, (B, C) = h Wbc, dt = h Wdt; u and (B, C) go through a
causal depthwise conv of width 4 and SiLU; dt = softplus(dt + dt_bias);
per head, S_t = exp(-dt exp(A_log)) S_{t-1} + B_t (dt u_t)^T and
y_t = C_t S_t + D u_t; out = RMSNorm(y * silu(z)) Wout; x += out.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int):
    """A PRNG key for any whole-number seed (more than 32 bits allowed)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def dims(m: dict) -> Dict[str, int]:
    d = m["d_model"]
    di = m["ssm"]["expand"] * d
    return {"d": d, "di": di, "H": di // m["ssm"]["head_dim"],
            "N": m["ssm"]["d_state"], "P": m["ssm"]["head_dim"],
            "dc": m["ssm"]["d_conv"], "V": m["vocab_size"],
            "G": m["n_layers"] // len(m["pattern"]), "L": m["n_layers"]}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _weight_specs(m: dict):
    """(path, shape, init) for every weight; init is ("normal", std) or a
    ("const"/"alog"/"dtbias", ...) rule."""
    k = dims(m)
    d, di, H, N, dc, G, L = (k["d"], k["di"], k["H"], k["N"], k["dc"],
                              k["G"], k["L"])
    out_scale = 1.0 / math.sqrt(2 * L)
    specs = [(("embed",), (k["V"], d), ("normal", 0.02))]
    for i, _ in enumerate(m["pattern"]):
        pre = ("groups", i)
        specs += [
            (pre + ("norm", "scale"), (G, d), ("const", 1.0)),
            (pre + ("mixer", "wz"), (G, d, di), ("normal", 1 / math.sqrt(d))),
            (pre + ("mixer", "wx"), (G, d, di), ("normal", 1 / math.sqrt(d))),
            (pre + ("mixer", "wbc"), (G, d, 2 * N),
             ("normal", 1 / math.sqrt(d))),
            (pre + ("mixer", "wdt"), (G, d, H), ("normal", 1 / math.sqrt(d))),
            (pre + ("mixer", "conv_x_w"), (G, dc, di),
             ("normal", 1 / math.sqrt(dc))),
            (pre + ("mixer", "conv_x_b"), (G, di), ("const", 0.0)),
            (pre + ("mixer", "conv_bc_w"), (G, dc, 2 * N),
             ("normal", 1 / math.sqrt(dc))),
            (pre + ("mixer", "conv_bc_b"), (G, 2 * N), ("const", 0.0)),
            (pre + ("mixer", "A_log"), (G, H), ("alog",)),
            (pre + ("mixer", "D"), (G, H), ("const", 1.0)),
            (pre + ("mixer", "dt_bias"), (G, H), ("dtbias",)),
            (pre + ("mixer", "norm", "scale"), (G, di), ("const", 1.0)),
            (pre + ("mixer", "out_proj"), (G, di, d),
             ("normal", out_scale / math.sqrt(di))),
        ]
    specs += [(("final_norm", "scale"), (d,), ("const", 1.0))]
    if not m.get("tie_embeddings"):
        specs += [(("lm_head",), (d, k["V"]), ("normal", 1 / math.sqrt(d)))]
    return specs


def _nest(flat):
    """{path: array} -> the nested tree (ints in a path index tuples)."""
    tree: dict = {}
    for path, arr in flat.items():
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = arr

    def fix(node):
        if not isinstance(node, dict):
            return node
        node = {k: fix(v) for k, v in node.items()}
        if node and all(isinstance(k, int) for k in node):
            return tuple(node[i] for i in range(len(node)))
        return node
    return fix(tree)


def make_weights(m: dict, seed: int):
    """Every weight, on the device, from the seed, in one jitted call."""
    specs = _weight_specs(m)
    H = dims(m)["H"]

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
    return sum(4 * int(np.prod(s)) for _, s, _ in _weight_specs(m))


# ---------------------------------------------------------------------------
# the plain forward pass
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _conv(u, w, b):
    """Causal depthwise conv over time: u (S, C), w (dc, C)."""
    dc = w.shape[0]
    up = jnp.pad(u, ((dc - 1, 0), (0, 0)))
    out = sum(up[i:i + u.shape[0]] * w[i] for i in range(dc))
    return out + b


def int4(x):
    """Round to a 4-bit format: groups of 16 along the last axis share a
    power-of-two scale, each value keeps a sign and 3 bits (-7..7).  The
    control for the recurrent state, which the configuration keeps in an
    8-bit format (MX8)."""
    shape = x.shape
    g = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 16, 16))
    amax = jnp.max(jnp.abs(g), -1, keepdims=True)
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.where(amax > 0, amax, 7.0) / 7.0)))
    return (jnp.clip(jnp.round(g / scale), -7, 7) * scale).reshape(shape)


def _mamba2(p, h, m, eps, quant=None):
    k = dims(m)
    H, N, P = k["H"], k["N"], k["P"]
    S = h.shape[0]
    z = h @ p["wz"]
    u = jax.nn.silu(_conv(h @ p["wx"], p["conv_x_w"], p["conv_x_b"]))
    bc = jax.nn.silu(_conv(h @ p["wbc"], p["conv_bc_w"], p["conv_bc_b"]))
    Bm, Cm = bc[:, :N], bc[:, N:]
    dt = jax.nn.softplus((h @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])
    decay = jnp.exp(-dt * jnp.exp(p["A_log"].astype(jnp.float32)))  # (S,H)
    uh = u.reshape(S, H, P).astype(jnp.float32)

    def step(state, inp):                          # state (H, N, P) f32
        a, b, c, x, t = inp
        state = a[:, None, None] * state + b[None, :, None] * (
            x * t[:, None])[:, None, :]
        if quant is not None:
            state = quant(state)
        return state, jnp.einsum("n,hnp->hp", c, state)

    _, y = jax.lax.scan(step, jnp.zeros((H, N, P), jnp.float32),
                        (decay, Bm.astype(jnp.float32),
                         Cm.astype(jnp.float32), uh, dt))
    y = y + p["D"].astype(jnp.float32)[None, :, None] * uh
    y = y.reshape(S, H * P).astype(h.dtype)
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    g = _rmsnorm(g, p["norm"]["scale"], eps).astype(h.dtype)
    return g @ p["out_proj"]


def _forward(w, tokens, m: dict, dtype, quant):
    eps = m.get("norm_eps", 1e-5)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    x = w["embed"][tokens].astype(dtype)

    def group(x, gw):
        for p in gw:                                   # one Mamba-2 layer each
            p = cast(p)
            x = x + _mamba2(p["mixer"], _rmsnorm(x, p["norm"]["scale"], eps),
                            m, eps, quant)
        return x, None

    x, _ = jax.lax.scan(group, x, w["groups"])
    x = _rmsnorm(x, w["final_norm"]["scale"].astype(dtype), eps)
    head = w["embed"].T if m.get("tie_embeddings") else w["lm_head"]
    return (x @ head.astype(dtype)).astype(jnp.float32)


#: control -> (compute type, matmul precision, rounding of the state after
#: every step).  The configurations state float32 at the ``highest``
#: precision and an 8-bit state: ``high`` is three bf16 passes on a TPU,
#: ``bf16`` bfloat16 weights and activations, ``int4`` a 4-bit state.
CONTROLS = {"high": (jnp.float32, "high", None),
            "bf16": (jnp.bfloat16, "highest", None),
            "int4": (jnp.float32, "highest", int4)}


def reference_logits(w, tokens, m: dict, control: Optional[str] = None):
    """Logits (S, V) of the plain forward pass over ``tokens`` (S,); with
    ``control`` (a key of ``CONTROLS``) the pass one precision lower."""
    dtype, precision, quant = CONTROLS.get(control,
                                           (jnp.float32, "highest", None))
    with jax.default_matmul_precision(precision):
        return _forward(w, tokens, m, dtype, quant)


def make_compare_fn(m: dict, controls: Sequence[str]):
    """Jitted (weights, tokens (S,), targets (S,), mask (S,), at) ->
    (reference logits at position ``at``, widest gap of the served tokens,
    {control: (widest gap of its own first tokens, its logits at ``at``)}).

    At each masked position i, ``targets[i]`` is the token served after
    position i; a gap is ``max(ref_i) - ref_i[token]`` under the float32
    reference."""
    def fn(w, tokens, targets, mask, at):
        ref = reference_logits(w, tokens, m)
        best = ref.max(-1)
        widest = lambda t: jnp.max(jnp.where(
            mask, best - jnp.take_along_axis(ref, t[:, None], -1)[:, 0], 0.0))
        ctl = {}
        for c in controls:
            lc = reference_logits(w, tokens, m, c)
            ctl[c] = (widest(jnp.argmax(lc, -1)), lc[at])
        return ref[at], widest(targets), ctl
    return jax.jit(fn)


def rel_err(got, ref) -> float:
    """Norm of the difference over the reference's norm."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def served_readings(w, m: dict, requests, pad_to: int,
                    controls: Sequence[str] = ()
                    ) -> Tuple[Dict[str, Dict[str, float]], int]:
    """The numbers compared, for the program and for each control standing
    in for it, over ``requests`` [(prompt ids, served ids, prefilled length,
    the program's logits at its last prefilled position, or None where it
    ran no prefill)], each run once
    through the reference, padded to ``pad_to`` tokens (one compile).

    ``prefill_err``: the widest ``rel_err`` of the prefill's logits against
    the reference's at the same position.  ``max_gap``: the widest gap of a
    served token (a control's own first token).  Returns ({"program" or
    control: {name: reading}}, tokens compared)."""
    fn = make_compare_fn(m, tuple(controls))
    sides = ("program",) + tuple(controls)
    out = {s: {"prefill_err": 0.0, "max_gap": 0.0} for s in sides}
    n = 0
    for prompt, served, s0, logits in requests:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        S = len(seq)
        assert S <= pad_to, (S, pad_to)
        tokens = np.zeros(pad_to, np.int32)
        tokens[:S] = seq
        targets = np.zeros(pad_to, np.int32)
        mask = np.zeros(pad_to, bool)
        p0 = len(prompt) - 1                 # logits here give served[0]
        targets[p0:p0 + len(served)] = served
        mask[p0:p0 + len(served)] = True
        ref_at, gap, ctl = fn(w, tokens, targets, mask, np.int32(s0 - 1))
        got = {"program": (gap, logits)}
        got.update(ctl)
        for side, (g, at) in got.items():
            r = out[side]
            r["max_gap"] = max(r["max_gap"], float(g))
            err = float("inf") if at is None else rel_err(at, ref_at)
            r["prefill_err"] = max(r["prefill_err"], err)
        n += len(served)
    return out, n
