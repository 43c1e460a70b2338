import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init).  This module is the only place that requests 512
placeholder devices; smoke tests and benchmarks see the single real CPU.

Per cell this driver:
  1. builds the production mesh ((16,16) or (2,16,16)),
  2. builds ShapeDtypeStruct stand-ins for params / optimizer / inputs,
  3. jits the step with explicit in/out shardings and ``.lower().compile()``,
  4. records memory_analysis(), cost_analysis(), and the collective schedule
     parsed from the partitioned HLO into a JSON artifact for §Roofline.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import ops as OPS
from repro.analysis import roofline as RL
from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, cell_supported, get_config
from repro.dist import sharding as SH
from repro.launch import specs as SP
from repro.launch.mesh import make_parallel
from repro.models import model as M
from repro.models.config import SHAPES
from repro.train import optimizer as O
from repro.train.train_loop import make_train_step

# the dry run forces the jnp backend: interpret-mode pallas would trace its
# grid as an unrolled Python loop (compile-time explosion at production
# sizes) and distort cost analysis -- see repro/ops/state_update.py
DRYRUN_QUANT = OPS.StateQuantConfig(fmt="mx8", rounding="stochastic",
                                    backend="jnp")


def dryrun_config(arch: str, **overrides):
    # fail fast if the forced (op, format, backend) triple ever unregisters
    for kind in OPS.OP_KINDS:
        OPS.resolve_backend(kind, DRYRUN_QUANT.fmt, DRYRUN_QUANT.backend,
                            strict=True)
    cfg = get_config(arch).with_(
        param_dtype="bfloat16",
        state_quant=DRYRUN_QUANT,
        scan_layers=True,
        remat=True,
    )
    return cfg.with_(**overrides) if overrides else cfg


def _mem_dict(mem) -> Dict[str, float]:
    return {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "peak_estimate_bytes": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
    }


# production tuning choices per cell (recorded in EXPERIMENTS.md):
# zamba2 train microbatches 2x -- its shared blocks' 2 d_model-wide
# attention holds the largest working set of the fleet.
CELL_TUNING = {
    ("zamba2-2.7b", "train_4k"): {"grad_accum": 2},
    # 236B on 256 chips: ZeRO moments alone are 7.4 GiB/chip; microbatch 4x
    # to bound activations
    ("deepseek-v2-236b", "train_4k"): {"grad_accum": 8},
    # the mLSTM chunk-state residuals are the big ticket; microbatch 2x
    ("xlstm-1.3b", "train_4k"): {"grad_accum": 8},
}


def _compile_step(cfg, sc, par, p_shapes, p_shard, grad_accum: int = 1,
                  serve_2d: bool = False):
    """jit+lower+compile the cell's step function; returns compiled exe.

    serve_2d: Pope-style 2D weight-stationary serving -- weights stay
    sharded over (data x model), the batch is replicated, caches shard their
    time axis over BOTH mesh axes, and per-layer activations are all-reduced
    instead of gathering P/tp weight bytes every token."""
    if sc.kind == "train":
        opt = O.OptimizerConfig()
        o_shapes = jax.eval_shape(lambda p: O.init_opt_state(p, opt), p_shapes)
        o_shard = SH.opt_state_shardings(o_shapes, p_shard, par)
        b_shapes = SP.batch_struct(cfg, sc)
        b_shard = SH.batch_shardings(b_shapes, par)
        step = make_train_step(cfg, opt, par=par, grad_accum=grad_accum)
        out_shapes = jax.eval_shape(step, p_shapes, o_shapes, b_shapes)
        m_shard = jax.tree.map(lambda _: SH.replicated(par), out_shapes[2])
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, m_shard),
                         donate_argnums=(0, 1))
        return jitted.lower(p_shapes, o_shapes, b_shapes).compile()
    if sc.kind == "prefill":
        b_shapes = SP.batch_struct(cfg, sc)
        b_shard = SH.batch_shardings(b_shapes, par)

        def prefill_step(params, batch):
            return M.prefill(params, cfg, batch, mesh_axes=par)

        out_shapes = jax.eval_shape(prefill_step, p_shapes, b_shapes)
        out_shard = _prefill_out_shardings(out_shapes, cfg, par, sc)
        jitted = jax.jit(prefill_step, in_shardings=(p_shard, b_shard),
                         out_shardings=out_shard)
        return jitted.lower(p_shapes, b_shapes).compile()
    # decode
    tok_s, len_s, cache_shapes = SP.decode_struct(cfg, sc)
    if serve_2d:
        # batch replicated; cache time axis over (data x model)
        c_shard = SH.cache_shardings(cache_shapes, cfg, par, 1)
        t_shard = SH.replicated(par)
    else:
        c_shard = SH.cache_shardings(cache_shapes, cfg, par, sc.global_batch)
        t_shard = SH.batch_shardings(tok_s, par) \
            if sc.global_batch % par.batch_size_divisor == 0 \
            else SH.replicated(par)

    def serve_step(params, tokens, lengths, caches):
        return M.decode_step(params, cfg, tokens, caches, lengths,
                             seed=0, mesh_axes=par)

    out_shapes = jax.eval_shape(serve_step, p_shapes, tok_s, len_s,
                                cache_shapes)
    logits_shard = _logits_sharding(out_shapes[0], cfg, par,
                                    sc if not serve_2d else
                                    dataclasses.replace(sc, global_batch=1))
    jitted = jax.jit(serve_step,
                     in_shardings=(p_shard, t_shard, t_shard, c_shard),
                     out_shardings=(logits_shard, c_shard),
                     donate_argnums=(3,))
    return jitted.lower(p_shapes, tok_s, len_s, cache_shapes).compile()


def _probe_costs(compiled, par) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = RL.parse_collectives(hlo, default_group=par.mesh.shape[par.model_axis])
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "link_bytes": coll.total_link_bytes,
            "collectives": coll.by_kind,
            "n_collectives": coll.op_count}


def _slstm_correction(cfg, sc, par) -> Dict[str, float]:
    """Analytic cost for sLSTM inner time-step loops.

    The per-token recurrence cannot be unrolled for the cost probe (S steps);
    its per-step cost is added analytically (recurrent einsum + gates)."""
    n_sl = cfg.pattern.count("slstm") * cfg.n_groups
    if n_sl == 0 or sc.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    from repro.models.ssm import _slstm_dims
    H, dh = _slstm_dims(cfg)
    data_sz = par.batch_size_divisor
    b_loc = max(sc.global_batch // data_sz, 1)
    per_step_flops = 2.0 * b_loc * H * dh * 4 * dh + 30.0 * b_loc * H * dh
    per_step_bytes = 12.0 * b_loc * H * dh * 4
    mult = 3.0 if sc.kind == "train" else 1.0     # fwd + bwd + remat
    steps = sc.seq_len - 1                         # probe counted one step
    return {"flops": n_sl * steps * per_step_flops * mult,
            "bytes": n_sl * steps * per_step_bytes * mult}


def lower_cell(arch: str, shape: str, multi_pod: bool = False,
               cfg_overrides: Optional[dict] = None,
               verbose: bool = True, skip_probe: bool = False,
               probe_from: Optional[Dict[str, Any]] = None,
               serve_2d: bool = False) -> Dict[str, Any]:
    """Lower+compile one cell; returns the roofline record.

    Compilations per cell:
      1. the production step (scan-over-layers, flash chunking) -- this is
         the deployment artifact; memory_analysis comes from here, and this
         compile succeeding IS the dry-run pass criterion.
      2. a FLOPs probe (XLA's cost_analysis counts while bodies ONCE, so the
         production HLO under-reports FLOPs): inner scans unrolled, layer
         loop unrolled at 1- and 2-group depth, extrapolated linearly to the
         full depth.  sLSTM time loops are corrected analytically.

    HBM and ICI byte terms use the analytic models in analysis/roofline.py
    (XLA:CPU's bytes-accessed reflects CPU-backend fusion, not TPU); the
    HLO-parsed numbers are kept in the record as diagnostics.

    ``probe_from``: reuse another mesh's probe, rescaled by per-chip token
    share (used for the multi-pod pass: same model, 2x the data shards).
    """
    sc = SHAPES[shape]
    ok, reason = cell_supported(arch, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    t0 = time.time()
    par = make_parallel(multi_pod=multi_pod)
    cfg = dryrun_config(arch, **(cfg_overrides or {}))
    tuning = CELL_TUNING.get((arch, shape), {})
    grad_accum = tuning.get("grad_accum", 1)
    n_chips = int(np.prod(list(par.mesh.shape.values())))
    pods = par.mesh.shape.get("pod", 1)

    p_shapes = SP.params_struct(cfg)
    p_shard = SH.param_shardings(p_shapes, cfg, par)
    n_params = RL.count_params(p_shapes)

    with par.mesh:
        compiled = _compile_step(cfg, sc, par, p_shapes, p_shard, grad_accum,
                                 serve_2d=serve_2d)
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    hlo_diag = _probe_costs(compiled, par)

    # ---- FLOPs probe ----
    pat = len(cfg.pattern)
    pre = len(cfg.prelude)
    if probe_from is not None and probe_from.get("status") == "ok":
        scale = probe_from["n_chips"] / n_chips
        flops_per_chip = probe_from["flops_per_chip"] * scale
        probe_diag = {"reused_from_chips": probe_from["n_chips"]}
    elif not skip_probe:
        # attention-free architectures have FLOPs linear in S (chunked LA is
        # O(S*c) intra + O(S/c * dk*dv) inter); probe at reduced seq and
        # scale back -- exact, and keeps the unrolled probe compile tractable
        has_attn = (any(k in ("attn", "mla") for k in cfg.pattern + cfg.prelude)
                    or cfg.shared_attn)
        if not has_attn and sc.kind in ("train", "prefill") \
                and sc.seq_len > 4096:
            sc_probe = dataclasses.replace(sc, seq_len=4096)
            s_scale = sc.seq_len / sc_probe.seq_len
        else:
            sc_probe, s_scale = sc, 1.0
        ks = (2, 4) if cfg.n_groups >= 4 else (1, 2)
        probes = {}
        # probe with large LA chunks: the unrolled chunk count drives probe
        # compile time, while intra-chunk FLOPs (the only c-dependent term,
        # O(S*c*dk) vs the O(S*dk*dv) state term) shift by <2% of the total
        probe_ssm = (dataclasses.replace(cfg.ssm, chunk=512)
                     if cfg.ssm is not None else None)
        def probe(k, apps=()):
            # ``apps``: the probe's shared-block schedule (hybrids); the
            # depth probes run without one
            cfg_k = cfg.with_(cost_probe=True, scan_layers=False,
                              n_layers=pre + k * pat, ssm=probe_ssm,
                              attn_q_chunk=4096, attn_kv_chunk=4096,
                              hybrid_layer_ids=apps)
            pk_shapes = SP.params_struct(cfg_k)
            pk_shard = SH.param_shardings(pk_shapes, cfg_k, par)
            with par.mesh:
                # grad_accum=1: the microbatch loop is a while body that
                # cost_analysis counts once; accumulation doesn't change FLOPs
                ck = _compile_step(cfg_k, sc_probe, par, pk_shapes, pk_shard, 1)
            return _probe_costs(ck, par)

        for k in ks:
            probes[k] = probe(k)
        corr = _slstm_correction(cfg, sc_probe, par)
        k1, k2 = ks
        delta = (probes[k2]["flops"] - probes[k1]["flops"]) / (k2 - k1)
        if delta > 0:
            flops = probes[k2]["flops"] + (cfg.n_groups - k2) * delta
        else:
            # GSPMD partitioned the two probe depths differently; fall back
            # to scaling the deeper probe by group count
            flops = probes[k2]["flops"] * cfg.n_groups / k2
        if cfg.n_shared_apps:
            # a shared-block application costs the same wherever it runs:
            # the deeper probe once more with one before each of its layers
            per_app = (probe(k2, tuple(range(k2)))["flops"]
                       - probes[k2]["flops"]) / k2
            flops += cfg.n_shared_apps * per_app
        flops_per_chip = (flops + corr["flops"]) * s_scale
        probe_diag = {f"probe{k1}_flops": probes[k1]["flops"],
                      f"probe{k2}_flops": probes[k2]["flops"],
                      "slstm_corr_flops": corr["flops"],
                      "seq_scale": s_scale}
    else:
        flops_per_chip = hlo_diag["flops"]
        probe_diag = {"unscaled_hlo": True}

    ac = RL.analytic_cost(cfg, sc, chips=n_chips, tp=par.tp, fs=par.fsdp,
                          pods=pods, n_params=n_params, grad_accum=grad_accum,
                          serve_2d=serve_2d)

    if sc.kind == "train":
        tokens = sc.global_batch * sc.seq_len
        model_flops = RL.model_flops_train(_active_params(cfg, n_params), tokens)
    elif sc.kind == "prefill":
        tokens = sc.global_batch * sc.seq_len
        model_flops = (2.0 / 6.0) * RL.model_flops_train(
            _active_params(cfg, n_params), tokens)
    else:
        model_flops = RL.model_flops_decode(_active_params(cfg, n_params),
                                            sc.global_batch)

    rf = RL.Roofline(flops_per_chip, ac["hbm_bytes"], ac["link_bytes"],
                     model_flops=model_flops, n_chips=n_chips)
    rec = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod,
        "status": "ok", "n_chips": n_chips, "n_params": n_params,
        "kind": sc.kind, "tuning": tuning, "serve_2d": serve_2d,
        "flops_per_chip": flops_per_chip,
        "hbm_bytes_per_chip": ac["hbm_bytes"],
        "link_bytes_per_chip": ac["link_bytes"],
        "cache_bytes_total": ac["cache_bytes_total"],
        "hlo_diag": hlo_diag,            # CPU-backend cost/collective parse
        "probe": probe_diag,
        "memory": _mem_dict(mem),
        "roofline": rf.row(),
        "model_flops": model_flops,
        "elapsed_s": round(time.time() - t0, 1),
        "hlo_bytes": len(hlo),
    }
    if verbose:
        mm = rec["memory"]
        print(f"[{arch} x {shape} x {'2pod' if multi_pod else '1pod'}] OK "
              f"args={mm['argument_bytes']/2**30:.2f}GiB "
              f"temp={mm['temp_bytes']/2**30:.2f}GiB "
              f"t_comp={rf.t_compute*1e3:.2f}ms t_mem={rf.t_memory*1e3:.2f}ms "
              f"t_coll={rf.t_collective*1e3:.2f}ms -> {rf.bottleneck} "
              f"({rec['elapsed_s']:.0f}s)",
              flush=True)
    return rec


def _active_params(cfg, n_params: float) -> float:
    """Active params per token (MoE: routed top_k + shared only)."""
    if cfg.moe is None:
        return n_params
    mc = cfg.moe
    expert_p = 3 * cfg.d_model * mc.d_expert      # wi, wg, wo per expert
    n_moe_layers = cfg.n_layers - len(cfg.prelude)
    inactive = (mc.n_experts - mc.top_k) * expert_p * n_moe_layers
    return n_params - inactive


def _logits_sharding(logits_shape, cfg, par, sc):
    dims = [None] * len(logits_shape.shape)
    if sc.global_batch % par.batch_size_divisor == 0:
        dims[0] = par.batch_axes
    if logits_shape.shape[-1] % par.tp == 0:
        dims[-1] = par.model_axis
    return par.named(jax.sharding.PartitionSpec(*dims))


def _prefill_out_shardings(out_shapes, cfg, par, sc):
    logits_s, cache_s = out_shapes
    lsh = _logits_sharding(logits_s, cfg, par, sc)
    if cache_s is None:
        return (lsh, None)
    csh = SH.cache_shardings(cache_s, cfg, par, sc.global_batch)
    return (lsh, csh)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch (default: sweep)")
    ap.add_argument("--archs", default=None,
                    help="comma-separated subset to sweep")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--include-paper-models", action="store_true")
    ap.add_argument("--resume", default=None,
                    help="existing results json: completed cells are kept")
    args = ap.parse_args(argv)

    if args.arch:
        archs = [args.arch]
    elif args.archs:
        archs = args.archs.split(",")
    else:
        archs = list(ALL_ARCHS if args.include_paper_models else ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    done = {}
    if args.resume:
        try:
            for rec in json.load(open(args.resume)):
                if rec.get("status") in ("ok", "skipped"):
                    done[(rec["arch"], rec["shape"],
                          bool(rec.get("multi_pod")))] = rec
            print(f"resuming: {len(done)} cells already complete", flush=True)
        except FileNotFoundError:
            pass

    results = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            prior = None
            for mp in sorted(meshes):         # single-pod first: probe reuse
                if (arch, shape, mp) in done:
                    rec = done[(arch, shape, mp)]
                    if not mp:
                        prior = rec
                    results.append(rec)
                    continue
                try:
                    rec = lower_cell(arch, shape, multi_pod=mp,
                                     probe_from=prior if mp else None)
                    if not mp:
                        prior = rec
                except Exception as e:  # a failure here is a framework bug
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                    print(f"[{arch} x {shape} x "
                          f"{'2pod' if mp else '1pod'}] FAILED: {e}",
                          flush=True)
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"\n{len(results)} cells, {failures} failures -> {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
