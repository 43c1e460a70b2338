#!/usr/bin/env python3
"""Chip smoke test: the paged serving path at zamba2-2.7b width on a TPU.

    python3 chip_smoke.py               # one chip: serve, check, compare
    python3 chip_smoke.py --chips 4     # one host with four chips: sharded
                                        # decode + train steps vs one chip

One chip: the full zamba2-2.7b config (54 Mamba-2 layers + two shared
attention blocks over nine applications, f32 weights from a seed) serves 8 greedy requests through
``repro.serving.api.Engine`` on the paged backend, with MX8 state and every
SPU op kind resolved strictly to its compiled Pallas kernel.  The workload
runs twice: the first pass compiles every shape, the second must compile
nothing.  Then one decode step with all 8 rows live over contexts of 2-3
pages runs through the pallas and the ``jnp`` paged pools from identical
contents: the largest logit difference must stay within ``LOGIT_RTOL`` of
the largest logit, and two planted faults (every length one too short;
two rows' second pages swapped) run through the pallas pool must exceed it.

Four chips (``--chips 4``): the sharded zamba2-2.7b ``decode_step`` on a
``data=1, model=4`` mesh at full width and depth, and two sharded train
steps at full width over the first seven layers and the shared-block
application before the seventh on a ``data=2, model=2`` mesh,
each against the same computation on one chip of the host.

Every check failure, in any phase, exits nonzero.  The last line of stdout
is ``{"ok": true, "device": {...}}`` and is printed only when all passed.
Without a TPU the script exits nonzero before any work; ``--no-device-check
--smoke-size`` rehearses the one-chip path on the CPU (Pallas interpret
mode) at smoke size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "zamba2-2.7b"
#: prompt lengths: short (bucketed prefill + a streamed tail), one token
#: short of a page, page-exact, one past a page, and multi-page prompts
#: whose tails stream through the decode batch (prefill_chunk = 128)
PROMPT_LENS = (40, 64, 100, 127, 128, 129, 200, 300)
MAX_NEW = 16
BATCH = 8
PREFILL_BUCKETS = (32, 64, 128)
#: the reference step: pages prefilled per row, and each row's length (it
#: reads 2 or 3 pages; 256 appends on a page boundary)
REF_PAGES = 3
REF_LENS = (130, 160, 200, 255, 256, 257, 300, 383)
#: pallas-vs-jnp tolerance, relative to the largest |logit| of the jnp
#: reference: one MX8 mantissa step (2^-6 of a group's scale; see CHANGES.md)
LOGIT_RTOL = 2.0 ** -6
#: sharded-vs-one-chip tolerance, relative to the largest |reference value|
SHARD_RTOL = 2.0 ** -6
SPU_KINDS = ("state_update", "kv_append", "attn_decode", "spec_verify")
SEED = 0                       # weights, prompts and sampling


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def device_summary(jax):
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its monitoring
    events), apart from the time programs run."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


class CallCounter:
    """Counts the calls through one attribute of an object."""

    def __init__(self, owner, attr: str):
        self.fn, self.n = getattr(owner, attr), 0
        setattr(owner, attr, self)

    def __call__(self, *args, **kwargs):
        self.n += 1
        return self.fn(*args, **kwargs)


def kernel_counts(lowered_text: str) -> dict:
    """tpu_custom_call ops per SPU kind (kernels are named spu_<kind>)."""
    import re
    names = re.findall(r'kernel_name = "spu_(\w+)"', lowered_text)
    return {k: names.count(k) for k in SPU_KINDS}


# ---------------------------------------------------------------------------
# one chip: the paged serving path
# ---------------------------------------------------------------------------

def serve_phase(args, jax, on_tpu: bool) -> None:
    from functools import partial

    import jax.numpy as jnp
    import numpy as np
    from repro import ops as OPS
    from repro.configs import get_config, get_smoke_config
    from repro.core.paged import PAGE_TOKENS
    from repro.models import model as M
    from repro.serving.api import Engine, ServeConfig
    from repro.serving.memory import PagedStatePool
    from repro.serving.sampler import SamplingConfig

    dev = jax.devices()[0]
    clock = CompileClock(jax)
    base = get_smoke_config(ARCH) if args.smoke_size else get_config(ARCH)

    # strict capability negotiation: every SPU kind on the paged layout
    # must resolve to its compiled Pallas kernel, never fall back to jnp
    for kind in SPU_KINDS:
        b = OPS.resolve_backend(kind, "mx8", "pallas", layout="paged",
                                strict=True)
        say(f"backend {kind}: {b}")
        check(b == "pallas", f"{kind} resolved to {b}, not pallas")
    quant = OPS.StateQuantConfig(fmt="mx8", rounding="stochastic",
                                 backend="pallas")
    cfg = base.with_(state_quant=quant)
    ref_cfg = base.with_(state_quant=OPS.StateQuantConfig(
        fmt="mx8", rounding="stochastic", backend="jnp"))

    t0 = time.perf_counter()
    params = M.init_model(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    n_bytes = sum(p.nbytes for p in jax.tree.leaves(params))
    say(f"init: {cfg.name} {n_params / 1e9:.3f} B params "
        f"({n_bytes / 2**30:.2f} GiB {cfg.param_dtype}) in "
        f"{time.perf_counter() - t0:.1f} s; peak_bytes_in_use="
        f"{peak_bytes(dev)}")

    scfg = ServeConfig(backend="paged", batch=BATCH,
                       prefill_buckets=PREFILL_BUCKETS,
                       sampling=SamplingConfig(temperature=0.0),
                       nan_guard=True, seed=SEED)
    eng = Engine(params, cfg, scfg)
    rng = np.random.default_rng(SEED)
    prefills = CallCounter(eng.engine, "_prefill")
    step_times = eng.engine.step_times     # host clock, one per decode step

    def serve_pass(label):
        handles = [eng.submit(rng.integers(0, cfg.vocab_size, n)
                              .astype(np.int32), max_new_tokens=MAX_NEW)
                   for n in PROMPT_LENS]
        c0, t = clock.seconds, time.perf_counter()
        s0, p0 = len(step_times), prefills.n
        eng.run()
        wall = time.perf_counter() - t
        compile_s = clock.seconds - c0
        steps = np.asarray(step_times[s0:])
        for h in handles:
            say(f"  {label} request {h.rid} prompt={len(h.request.prompt)} "
                f"status={h.status} tokens={len(h.output)}")
            check(h.status == "done" and len(h.output) == MAX_NEW,
                  f"request {h.rid} ended {h.status} with "
                  f"{len(h.output)} tokens")
        say(f"{label}: {len(handles)} requests in {wall:.2f} s wall "
            f"(compile {compile_s:.2f} s, run {wall - compile_s:.2f} s); "
            f"jit compiles so far {eng.obs.recompiles.n_events}")
        say(f"{label}: {len(steps)} decode steps ({steps.sum():.2f} s; "
            f"median {np.median(steps) * 1e3:.1f} ms, min "
            f"{steps.min() * 1e3:.1f} ms a step on the host clock, sampling "
            f"and the token read-back included); {prefills.n - p0} prefill "
            f"calls; {wall - steps.sum():.2f} s outside decode steps")
        return handles

    serve_pass("warmup")
    warm_compiles = eng.obs.recompiles.n_events
    serve_pass("serve")
    check(eng.obs.recompiles.n_events == warm_compiles,
          f"{eng.obs.recompiles.n_events - warm_compiles} jit compiles "
          "past warmup")
    say(f"jit compiles: {warm_compiles} (all in warmup) "
        f"{eng.obs.recompiles.counts()}")
    say(f"serve: peak_bytes_in_use={peak_bytes(dev)}")

    # the compiled kernels inside the lowered paged decode steps
    pool = eng.engine.pool
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    for step, toks in (("decode", i32(BATCH)), ("spec_decode",
                                                i32(BATCH, 4))):
        impl = pool._decode_impl if step == "decode" \
            else pool._decode_spec_impl
        text = jax.jit(impl).lower(params, pool.pools, i32(BATCH, 4),
                                   i32(BATCH), i32(BATCH), toks,
                                   jnp.int32(0)).as_text()
        counts = kernel_counts(text)
        say(f"lowered paged {step} step: {text.count('tpu_custom_call')} "
            f"tpu_custom_call {counts}")
        if on_tpu:
            need = ({"state_update", "kv_append", "attn_decode"}
                    if step == "decode"
                    else {"state_update", "kv_append", "spec_verify"})
            for kind in need:
                check(counts[kind] >= 1,
                      f"no {kind} kernel in the paged {step} step")
    n_slabs = pool.n_slabs
    del eng, pool

    # reference on the chip: one decode step with every row live over 2-3
    # pages, through the pallas and the jnp paged pools filled alike.
    # Prefill runs no SPU kernel, so one prefill feeds both pools.
    n_ctx = REF_PAGES * PAGE_TOKENS
    prefill = jax.jit(partial(M.prefill, cfg=cfg, mesh_axes=None))
    rows = []
    for _ in range(BATCH):
        t = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, n_ctx)),
                        jnp.int32)
        logits, row = prefill(params, batch={"tokens": t, "targets": t})
        check(bool(jnp.all(jnp.isfinite(logits))), "non-finite prefill")
        rows.append(row)
    rids = list(range(BATCH))
    toks = rng.integers(0, cfg.vocab_size, BATCH).astype(np.int32)
    lens = np.asarray(REF_LENS, np.int32)
    pools = {}
    for name, c in (("pallas", cfg), ("jnp", ref_cfg)):
        p = PagedStatePool(c, n_pages=scfg.n_pages, n_slabs=n_slabs)
        for rid, row in zip(rids, rows):
            check(p.register(rid, REF_PAGES), "reference pool full")
            p.insert_prefill(rid, row)
        pools[name] = p
    pal = pools["pallas"]
    check(pal.page_table == pools["jnp"].page_table,
          "reference pools placed pages differently")
    del rows
    want = np.asarray(pools["jnp"].decode(params, rids, toks, lens, seed=1))
    del pools["jnp"]
    before = jax.tree.map(jnp.copy, pal.pools)

    def pallas_step(lens, swap=False):
        pal.pools = jax.tree.map(jnp.copy, before)
        table = {r: list(p) for r, p in pal.page_table.items()}
        if swap:
            a, b = pal.page_table[0], pal.page_table[1]
            a[1], b[1] = b[1], a[1]
        out = np.asarray(pal.decode(params, rids, toks, lens, seed=1))
        pal.page_table.update(table)
        return out

    got = pallas_step(lens)
    scale = float(np.max(np.abs(want)))
    ratio = lambda a: float(np.max(np.abs(a - want))) / max(scale, 1e-30)
    check(bool(np.all(np.isfinite(got))), "non-finite pallas decode logits")
    per_row = np.max(np.abs(got - want), axis=-1) / scale
    say(f"reference decode ({BATCH} rows live, lengths {list(REF_LENS)}): "
        f"max|pallas - jnp| / max|logit| = {ratio(got):.4g} (max|logit| "
        f"{scale:.6g}, tolerance {LOGIT_RTOL:.4g}); per row "
        f"{[float(f'{r:.3g}') for r in per_row]}; argmax agrees in "
        f"{int(np.sum(got.argmax(-1) == want.argmax(-1)))} of {BATCH} rows")
    # both faults keep every append inside the row's registered pages
    faults = {"lengths - 1": ratio(pallas_step(lens - 1)),
              "rows 0 and 1 swap their second page": ratio(
                  pallas_step(lens, swap=True))}
    for what, r in faults.items():
        say(f"planted fault ({what}): ratio {r:.4g} vs tolerance "
            f"{LOGIT_RTOL:.4g}")
    check(ratio(got) <= LOGIT_RTOL,
          f"decode logits differ by {ratio(got)} x max|logit|")
    for what, r in faults.items():
        check(r > LOGIT_RTOL, f"the comparison misses a planted fault "
              f"({what}): ratio {r}")
    say(f"reference: peak_bytes_in_use={peak_bytes(dev)}; "
        f"compile total {clock.seconds:.1f} s")


# ---------------------------------------------------------------------------
# four chips: sharded decode and train steps
# ---------------------------------------------------------------------------

def _spans_all(jax, tree, n: int, what: str) -> None:
    for leaf in jax.tree.leaves(tree):
        check(len(leaf.sharding.device_set) == n,
              f"a {what} leaf {leaf.shape} spans "
              f"{len(leaf.sharding.device_set)} of {n} devices")


def _bytes_per_device(jax) -> str:
    return " ".join(f"{d.id}:{(d.memory_stats() or {}).get('bytes_in_use')}"
                    for d in jax.devices())


def _rel_diff(np, a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) / max(float(np.max(np.abs(b))),
                                               1e-30)


def sharded_phase(args, jax) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro import ops as OPS
    from repro.configs import get_config, get_smoke_config
    from repro.data.pipeline import make_batch_fn
    from repro.dist import sharding as SH
    from repro.launch.mesh import make_local_parallel
    from repro.models import model as M
    from repro.train import optimizer as O
    from repro.train.train_loop import make_train_step

    n = len(jax.devices())
    base = get_smoke_config(ARCH) if args.smoke_size else get_config(ARCH)
    # GSPMD cannot partition a compiled Mosaic kernel, so the sharded decode
    # (and its one-chip reference) runs the SPU ops' jnp backend
    base = base.with_(state_quant=OPS.StateQuantConfig(
        fmt="mx8", rounding="stochastic", backend="jnp"))

    # --- sharded decode_step, data=1 x model=4, full width and depth ---
    par = make_local_parallel(data=1, model=4)
    params = M.init_model(jax.random.PRNGKey(SEED), base)
    B, S = BATCH, 64
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              base.vocab_size)
    logits, caches = jax.jit(lambda p, t: M.prefill(
        p, base, {"tokens": t, "targets": t}))(params, toks)
    lengths = jnp.full((B,), S, jnp.int32)
    caches = M.set_cache_lengths(caches, lengths)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    step = lambda p, t, c, ln: M.decode_step(p, base, t, c, ln, seed=5)
    ref, _ = jax.jit(step)(params, tok, caches, lengths)
    ref = np.asarray(ref)
    p_shard = SH.param_shardings(params, base, par)
    c_shard = SH.cache_shardings(caches, base, par, global_batch=B)
    params_s = jax.device_put(params, p_shard)
    caches_s = jax.device_put(caches, c_shard)
    rep = SH.replicated(par)
    tok_s, len_s = jax.device_put(tok, rep), jax.device_put(lengths, rep)
    del params, caches
    _spans_all(jax, (params_s, caches_s, tok_s, len_s), n, "decode")
    with par.mesh:
        got, _ = jax.jit(step)(params_s, tok_s, caches_s, len_s)
    rel = _rel_diff(np, got, ref)
    say(f"sharded decode_step (data=1, model=4): rel max diff vs one chip "
        f"{rel:.3g} (tolerance {SHARD_RTOL:.3g}); bytes in use per device "
        f"{_bytes_per_device(jax)}")
    check(rel <= SHARD_RTOL, f"sharded decode differs by {rel}")
    del params_s, caches_s, got

    # --- two sharded train steps, data=2 x model=2, the layers up to and
    # including the first that takes a shared block ---
    first = base.hybrid_layer_ids[0]
    # unrolled: v5e's compiler fails a RET_CHECK in its scheduler on the
    # sharded train step over a scan of Mamba-2 layers
    cfg = base.with_(n_layers=first + 1, hybrid_layer_ids=(first,),
                     scan_layers=False)
    par = make_local_parallel(data=2, model=2)
    # a small step: at full width a 3e-4 Adam step already diverges (loss
    # 10.7 -> 19.9) and the second one leaves NaNs in both runs
    opt = O.OptimizerConfig(lr=1e-5, total_steps=2, warmup_steps=1)
    batch_fn = make_batch_fn(cfg, seq_len=128, global_batch=4)
    p1 = M.init_model(jax.random.PRNGKey(SEED), cfg)
    s1 = O.init_opt_state(p1, opt)
    # the host keeps the initial state for the sharded run; both runs
    # donate theirs, so one chip holds a single copy of weights + moments
    params, opt_state = jax.device_get((p1, s1))
    step1 = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    losses1 = []
    for i in range(2):
        p1, s1, m = step1(p1, s1, batch_fn(i))
        losses1.append(float(m["loss"]))
    p1 = jax.device_get(p1)
    del s1
    p_shard = SH.param_shardings(params, cfg, par)
    o_shard = SH.opt_state_shardings(opt_state, p_shard, par)
    p2 = jax.device_put(params, p_shard)
    s2 = jax.device_put(opt_state, o_shard)
    losses2 = []
    b_shard = SH.batch_shardings(batch_fn(0), par)
    step2 = jax.jit(make_train_step(cfg, opt, par=par),
                    in_shardings=(p_shard, o_shard, b_shard),
                    out_shardings=(p_shard, o_shard, None),
                    donate_argnums=(0, 1))
    with par.mesh:
        for i in range(2):
            b = jax.device_put(batch_fn(i), b_shard)
            _spans_all(jax, (p2, s2, b), n, "train")
            p2, s2, m = step2(p2, s2, b)
            losses2.append(float(m["loss"]))
    _spans_all(jax, (p2, s2), n, "trained")
    p2 = jax.device_get(p2)
    for leaf in jax.tree.leaves((p1, p2)):
        check(bool(np.all(np.isfinite(leaf))), "non-finite trained weights")
    diff = max(float(np.max(np.abs(a - b)))
               for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p1)))
    say(f"sharded train (data=2, model=2, {cfg.n_layers} layers): losses "
        f"{losses2} vs one chip {losses1}; params max diff {diff:.3g} "
        f"(lr {opt.lr}); bytes in use per device {_bytes_per_device(jax)}")
    for a, b in zip(losses2, losses1):
        check(abs(a - b) <= SHARD_RTOL * abs(b), f"loss {a} vs {b}")
    # an Adam step moves a weight by at most ~lr: two steps whose update
    # direction flips for a near-zero gradient stay within 4 lr
    check(diff <= 4 * opt.lr, f"sharded params differ by {diff}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the serving path; 4: sharded decode + train")
    ap.add_argument("--smoke-size", action="store_true",
                    help="the reduced config (CPU rehearsal)")
    ap.add_argument("--no-device-check", action="store_true",
                    help="run without a TPU (CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.no_device_check:
        print(f"chip_smoke: needs a TPU, found {platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    say(f"device: {device_summary(jax)}; compile cache "
        f"{enable_compile_cache()}")
    try:
        if args.chips == 4:
            sharded_phase(args, jax)
        else:
            serve_phase(args, jax, on_tpu=platform == "tpu")
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_summary(jax)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
