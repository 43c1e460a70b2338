"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is measured
wall time on this CPU where the benchmark executes real compute, or 0 for
purely analytical tables; ``derived`` is the figure-level quantity being
reproduced (a ratio, error, or tokens/s).

  fig3_latency_breakdown    state-update share of generation latency vs batch
  fig4_swamping             format x rounding accuracy study
  fig5a_pim_designs         time-mux / pipelined / interleaved PIM throughput
  fig6_area_accuracy        area (paper RTL numbers) x accuracy Pareto
  fig12_generation          end-to-end throughput: gpu / gpu+q / gpu+pim / pimba
  fig13_latency_reduction   per-op latency reduction vs baselines
  fig15_latency_memory      latency + cache memory vs output length
  kernel_state_update       fused kernel vs unfused jnp on CPU (interpret)
  kernel_attention          decode attention kernel vs ref
  serving_throughput        paged engine tokens/s, unbucketed and bucketed
                            prefill (tiny model, real compute)
  serving_open_loop         Poisson arrivals driving Engine.step(): goodput
  serving_shared_prefix     CoW fork vs N independent submissions: prefill
                            tokens + allocated pages saved
  serving_spec              speculative decoding: self-drafted greedy serving,
                            acceptance counters + pimsim verify-step speedup
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROWS: List[Tuple[str, float, str]] = []

# one artifact shared by the serving benches; each contributor rewrites the
# file so a partial run still leaves a valid BENCH_serving.json
SERVING_ARTIFACT: dict = {}


def _dump_serving_artifact():
    import json
    with open("BENCH_serving.json", "w") as f:
        json.dump(SERVING_ARTIFACT, f, indent=2, default=float)


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _timeit(fn: Callable, n: int = 5) -> float:
    fn()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------

def fig3_latency_breakdown():
    from repro.core import pimsim as PS
    sys_cfg = PS.SystemConfig()
    for name in ("retnet-2.7b", "gla-2.7b", "hgrn2-2.7b", "mamba2-2.7b",
                 "zamba2-7b"):
        spec = PS.PAPER_MODELS[name]
        for batch in (32, 128):
            lat = PS.generation_step_latency(spec, batch, 2048, sys_cfg, "gpu")
            frac = (lat["state"] + lat["attn"]) / lat["total"]
            emit(f"fig3/{name}/b{batch}", 0.0,
                 f"state+attn_frac={frac:.3f}")


def fig4_swamping():
    from repro.analysis.formats_study import run_swamping_study
    t0 = time.perf_counter()
    errs = run_swamping_study(T=300)
    dt = (time.perf_counter() - t0) * 1e6 / len(errs)
    for (fmt, rnd), e in sorted(errs.items(), key=lambda kv: kv[1]):
        emit(f"fig4/{fmt}/{rnd}", dt, f"state_rel_err={e:.4f}")


def fig5a_pim_designs():
    from repro.core import pimsim as PS
    sys_cfg = PS.SystemConfig()
    spec = PS.PAPER_MODELS["retnet-2.7b"]
    w16 = PS.StateWorkload(128, spec.n_layers, spec.n_heads, spec.dk,
                           spec.dv, "fp16")
    w8 = PS.StateWorkload(128, spec.n_layers, spec.n_heads, spec.dk,
                          spec.dv, "mx8")
    t_gpu = PS.gpu_state_update_latency(w16, sys_cfg)
    for design, w, paper in (("time_multiplexed", w16, 2.8),
                             ("pipelined", w16, 4.3),
                             ("pimba_mx8", w8, None)):
        t = PS.pim_state_update_latency(w, sys_cfg,
                                        design.replace("_mx8", ""))
        tag = f"x_vs_gpu={t_gpu/t:.2f}" + (f"(paper={paper})" if paper else "")
        emit(f"fig5a/{design}", 0.0, tag)


def fig6_area_accuracy():
    """Area numbers are the paper's RTL results (Table 3 / Fig 6, not
    re-synthesizable here); accuracy is our measured study."""
    from repro.analysis.formats_study import run_swamping_study
    area_mm2 = {"fp16": 0.081, "int8": 0.072, "mx8": 0.053,
                "fp8_e4m3": 0.048, "fp8_e5m2": 0.046}
    errs = run_swamping_study(T=200)
    for fmt in ("fp16", "int8", "mx8", "fp8_e4m3", "fp8_e5m2"):
        rnd = "stochastic" if fmt not in ("fp16",) else "nearest"
        e = errs[(fmt, rnd)]
        emit(f"fig6/{fmt}+{'sr' if rnd == 'stochastic' else 'rne'}", 0.0,
             f"area_mm2={area_mm2[fmt]};state_rel_err={e:.4f}")


def fig12_generation():
    from repro.core import pimsim as PS
    sys_cfg = PS.SystemConfig()
    gains_gpu, gains_pim = [], []
    for name, spec in PS.PAPER_MODELS.items():
        th = {s: PS.generation_throughput(spec, 128, 2048, sys_cfg, s)
              for s in ("gpu", "gpu_q", "gpu_pim", "pimba")}
        gains_gpu.append(th["pimba"] / th["gpu"])
        gains_pim.append(th["pimba"] / th["gpu_pim"])
        emit(f"fig12/{name}", 0.0,
             f"pimba_vs_gpu={th['pimba']/th['gpu']:.2f};"
             f"pimba_vs_gpupim={th['pimba']/th['gpu_pim']:.2f};"
             f"gpuq_vs_gpu={th['gpu_q']/th['gpu']:.2f}")
    emit("fig12/geomean", 0.0,
         f"vs_gpu={np.exp(np.mean(np.log(gains_gpu))):.2f}(paper~2.0);"
         f"vs_gpupim={np.exp(np.mean(np.log(gains_pim))):.2f}(paper~1.4)")


def fig13_latency_reduction():
    from repro.core import pimsim as PS
    sys_cfg = PS.SystemConfig()
    for name in ("retnet-2.7b", "hgrn2-2.7b", "zamba2-7b", "opt-6.7b"):
        spec = PS.PAPER_MODELS[name]
        for batch in (32, 128):
            l_gpu = PS.generation_step_latency(spec, batch, 2048, sys_cfg, "gpu")
            l_pb = PS.generation_step_latency(spec, batch, 2048, sys_cfg, "pimba")
            su = (l_gpu["state"] / l_pb["state"]) if l_pb["state"] else 0.0
            at = (l_gpu["attn"] / l_pb["attn"]) if l_pb["attn"] else 0.0
            emit(f"fig13/{name}/b{batch}", 0.0,
                 f"e2e={l_gpu['total']/l_pb['total']:.2f};state={su:.1f};"
                 f"attn={at:.1f}")


def fig15_latency_memory():
    from repro import ops as OPS
    from repro.core import pimsim as PS
    sys_cfg = PS.SystemConfig()
    spec = PS.PAPER_MODELS["zamba2-7b"]
    mx8 = OPS.StateQuantConfig(fmt="mx8", rounding="stochastic", backend="jnp")
    for out_len in (256, 1024, 4096):
        seq = 1024 + out_len
        lat = PS.generation_step_latency(spec, 128, seq, sys_cfg, "pimba")
        # memory: weights + resident state + mx8 KV, all sized by the ops'
        # own traffic descriptors (one read pass == the resident footprint)
        state = PS.StateWorkload(128, spec.n_layers, spec.n_heads, spec.dk,
                                 spec.dv, "mx8").state_bytes
        kv_plan = OPS.plan_attn_decode_dims(
            "attn_decode", dict(B=128, T=seq, KVH=spec.attn_kv_heads,
                                dk=spec.attn_head_dim, dv=spec.attn_head_dim,
                                n=1), mx8)
        mem = (spec.n_params * 2 + state
               + OPS.traffic(kv_plan).state_read * spec.attn_layers)
        emit(f"fig15/outlen{out_len}", 0.0,
             f"step_ms={lat['total']*1e3:.2f};mem_gb={mem/1e9:.1f}")


# ---------------------------------------------------------------------------

def kernel_state_update():
    from repro import ops as OPS
    from repro.core import formats as F
    B, H, dk, dv = 8, 8, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    S0 = jax.random.normal(ks[0], (B, H, dv, dk))
    d = jax.nn.sigmoid(jax.random.normal(ks[1], (B, H, dk)))
    k = jax.random.normal(ks[2], (B, H, dk))
    v = jax.random.normal(ks[3], (B, H, dv))
    q = jax.random.normal(ks[4], (B, H, dk))
    qS = F.mx8_quantize(S0)
    for backend in ("pallas", "jnp"):
        cfg = OPS.StateQuantConfig(fmt="mx8", rounding="stochastic",
                                   backend=backend)
        # the op's own traffic descriptor is the bandwidth denominator
        tr = OPS.traffic(OPS.plan_state_update_dims(B, H, dk, dv, cfg))
        fn = jax.jit(lambda s, cfg=cfg: OPS.state_update_step(
            qS, d, k, v, q, cfg, seed=s))
        us = _timeit(lambda: jax.block_until_ready(fn(jnp.int32(1))), n=3)
        emit(f"kernel/state_update/{backend}", us,
             f"GBps_logical={tr.state_total/us*1e6/1e9:.3f};"
             f"ai_flops_per_byte={6*dk*dv/(2*dk*dv):.1f}")
    # fp16 baseline (the paper's GPU configuration)
    Sf = S0.astype(jnp.bfloat16)
    fn = jax.jit(lambda s: OPS.state_update_float(Sf, d, k, v, q))
    us = _timeit(lambda: jax.block_until_ready(fn(0)), n=3)
    emit("kernel/state_update/fp16_baseline", us,
         f"GBps_logical={B*H*dk*dv*2*2/us*1e6/1e9:.3f}")


def kernel_attention():
    from repro import ops as OPS
    from repro.core import attention_cache as AC
    from repro.core import formats as F
    B, H, KVH, dh, T = 4, 8, 2, 128, 1024
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, dh))
    K = jax.random.normal(ks[1], (B, T, KVH, dh))
    V = jax.random.normal(ks[2], (B, T, KVH, dh))
    qK, qV = F.mx8_quantize(K), F.mx8_quantize(V)
    lengths = jnp.full((B,), T, jnp.int32)
    for backend in ("pallas", "jnp"):
        cfg = OPS.StateQuantConfig(fmt="mx8", rounding="nearest",
                                   backend=backend)
        cache = AC.KVCache(qK, qV, lengths, "mx8")
        tr = OPS.traffic(OPS.plan_attn_decode_dims(
            "attn_decode", dict(B=B, T=T, KVH=KVH, dk=dh, dv=dh, n=1, H=H),
            cfg))
        fn = jax.jit(lambda: OPS.attn_decode(cache, q, cfg))
        us = _timeit(lambda: jax.block_until_ready(fn()), n=3)
        emit(f"kernel/attention_decode/{backend}", us,
             f"GBps_logical={tr.state_read/us*1e6/1e9:.3f}")


def serving_throughput():
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.api import ServeConfig
    from repro.serving.engine import PagedServingEngine, Request
    cfg = get_smoke_config("mamba2-2.7b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    artifact = SERVING_ARTIFACT
    # one mixed prompt set shared by the unbucketed and bucketed rows
    mixed = [rng.integers(0, cfg.vocab_size,
                          8 + i % 8 if i % 2 else 40 + i).astype(np.int32)
             for i in range(8)]
    # decode runs the block-table-native ops (no per-step gather/scatter)
    eng = PagedServingEngine(params, cfg, ServeConfig(
        batch=4, n_pages=9, n_slabs=9, prefill_chunk=128))
    for i, prompt in enumerate(mixed):
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=8))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    stats = eng.stats()
    stats["bank_report"] = eng.bank_report()
    # the registry view: histogram summaries (ttft/step/tok-latency
    # percentiles, step_s split by compile tag) + which jitted fns compiled
    # how often -- the p99_step_s vs p99_step_nocompile_s gap is compile
    # stalls, not steady-state decode
    stats["histograms"] = eng.obs.metrics.summaries()
    stats["recompile_counts"] = eng.obs.recompiles.counts()
    artifact["paged"] = stats
    emit("serving/paged", dt / max(toks, 1) * 1e6,
         f"tokens_per_s={toks/dt:.2f};requests={len(done)};"
         f"gather_bytes={stats['gather_bytes']:.0f};"
         f"occupancy={stats['occupancy']:.2f};"
         f"fragmentation={stats['fragmentation']:.2f};"
         f"p99_ttft_ms={stats.get('p99_ttft_s', 0)*1e3:.1f};"
         f"p99_step_nocompile_ms="
         f"{stats['p99_step_nocompile_s']*1e3:.1f};"
         f"recompiles={stats['recompiles']:.0f}")
    # --- jit-hazard fix (lint rule JH103): prefill length bucketing -----
    # "before" is the unbucketed paged row above -- one prefill compile per
    # distinct prompt length (8 in this mix).  "after" snaps the prefill to
    # a fixed bucket set and streams the tail through the decode batch, so
    # the prefill jit sees one shape per *bucket*.
    eng_b = PagedServingEngine(params, cfg, ServeConfig(
        batch=4, n_pages=9, n_slabs=9, prefill_chunk=128,
        prefill_buckets=(8, 16, 32, 64, 128)))
    for i, prompt in enumerate(mixed):
        eng_b.submit(Request(rid=100 + i, prompt=prompt, max_new_tokens=8))
    t0 = time.perf_counter()
    done_b = eng_b.run()
    dt_b = time.perf_counter() - t0
    toks_b = sum(len(r.output) for r in done_b)
    stats_b = eng_b.stats()
    stats_b["recompile_counts"] = eng_b.obs.recompiles.counts()
    artifact["paged_bucketed"] = stats_b
    artifact["jit_hazard_fix"] = {
        "rule": "JH103 dynamic-shape-feeds-jit (prefill length churn)",
        "fix": "ServeConfig.prefill_buckets=(8, 16, 32, 64, 128)",
        "before": {k: stats[k] for k in
                   ("recompiles", "recompile_counts",
                    "p99_step_nocompile_s", "tokens_per_s")},
        "after": {k: stats_b[k] for k in
                  ("recompiles", "recompile_counts",
                   "p99_step_nocompile_s", "tokens_per_s")},
    }
    emit("serving/paged_bucketed", dt_b / max(toks_b, 1) * 1e6,
         f"tokens_per_s={toks_b/dt_b:.2f};requests={len(done_b)};"
         f"p99_ttft_ms={stats_b.get('p99_ttft_s', 0)*1e3:.1f};"
         f"p99_step_nocompile_ms="
         f"{stats_b['p99_step_nocompile_s']*1e3:.1f};"
         f"recompiles={stats_b['recompiles']:.0f}")
    _dump_serving_artifact()


def serving_open_loop():
    """Open-loop load generation: Poisson arrivals at a configurable rate
    drive `Engine.step()` (no drain-to-empty batching artifacts).  Emits
    goodput -- the fraction of requests whose end-to-end latency met a
    fixed deadline budget -- alongside achieved throughput."""
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.api import Engine, ServeConfig
    cfg = get_smoke_config("mamba2-2.7b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_req, max_new, budget_s = 8, 6, 2.0
    # one shared prompt length: a single prefill trace, so the measured
    # open-loop latency is decode scheduling, not compile time
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(n_req)]

    for rate in (5.0, 50.0):
        eng = Engine(params, cfg, ServeConfig(backend="paged", batch=4,
                                              n_pages=9, n_slabs=9))
        # jit caches are per-engine: warm *this* engine's prefill/decode
        # traces (full batch so the bucketed decode shape compiles too)
        # before the arrival clock starts, so goodput measures scheduling,
        # not XLA compile time
        for p in prompts[:4]:
            eng.submit(p, max_new_tokens=2)
        eng.run()
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
        handles = []
        t0 = time.perf_counter()
        nxt = 0
        while nxt < n_req or any(not h.finished for h in handles):
            now = time.perf_counter() - t0
            while nxt < n_req and arrivals[nxt] <= now:
                handles.append(eng.submit(prompts[nxt],
                                          max_new_tokens=max_new))
                nxt += 1
            if eng.has_work():
                eng.step()
            elif nxt < n_req:
                time.sleep(min(arrivals[nxt] - now, 1e-3))
        dt = time.perf_counter() - t0
        # metrics over the measured handles only (the warm-up batch is
        # excluded; engine.stats() would mix it in)
        lats = [h.request.t_done - h.request.t_submit for h in handles
                if h.status == "done"]
        ttfts = [h.request.t_first - h.request.t_submit for h in handles
                 if h.request.t_first > 0]
        goodput = sum(1 for L in lats if L <= budget_s) / n_req
        toks = sum(len(h.output) for h in handles)
        row = {
            "rate_rps": rate, "goodput": goodput,
            "deadline_budget_s": budget_s,
            "tokens_per_s": toks / max(dt, 1e-9),
            "p99_ttft_s": float(np.percentile(ttfts, 99)) if ttfts else 0.0,
            "p99_latency_s": float(np.percentile(lats, 99)) if lats else 0.0,
        }
        SERVING_ARTIFACT[f"open_loop_rate{rate:g}"] = row
        emit(f"serving/open_loop/rate{rate:g}", dt / n_req * 1e6,
             f"goodput={goodput:.2f};tokens_per_s={row['tokens_per_s']:.2f};"
             f"p99_ttft_ms={row['p99_ttft_s']*1e3:.1f}")
    _dump_serving_artifact()


def serving_shared_prefix():
    """Copy-on-write prefix sharing vs N independent submissions of the
    same prompt: fewer prefill tokens (the shared prefix is ingested once)
    and fewer allocated pages (full prefix pages are refcounted, only the
    tail page is copied per fork)."""
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.api import Engine, ServeConfig
    cfg = get_smoke_config("mamba2-2.7b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_forks, max_new = 4, 4
    prompt = rng.integers(0, cfg.vocab_size, 140).astype(np.int32)
    scfg = ServeConfig(backend="paged", batch=4, n_pages=17, n_slabs=11)

    # N independent submissions: every request re-prefills + re-pins
    eng_i = Engine(params, cfg, scfg)
    t0 = time.perf_counter()
    for _ in range(n_forks):
        eng_i.submit(prompt, max_new_tokens=max_new)
    eng_i.run()
    dt_i = time.perf_counter() - t0
    st_i = eng_i.stats()

    # one parent + N copy-on-write forks: prefix prefilled and pinned once
    eng_f = Engine(params, cfg, scfg)
    t0 = time.perf_counter()
    parent = eng_f.submit(prompt, max_new_tokens=1, retain=True)
    parent.result()
    kids = [eng_f.fork(parent, max_new_tokens=max_new)
            for _ in range(n_forks)]
    eng_f.run()
    dt_f = time.perf_counter() - t0
    st_f = eng_f.stats()
    assert all(k.status == "done" for k in kids)

    saved_tokens = st_i["prefill_tokens"] - st_f["prefill_tokens"]
    saved_pages = st_i["pages_allocated"] - st_f["pages_allocated"]
    SERVING_ARTIFACT["shared_prefix"] = {
        "n_forks": n_forks, "prompt_tokens": len(prompt),
        "independent": st_i, "forked": st_f,
        "prefill_tokens_saved": saved_tokens,
        "pages_saved": saved_pages,
        "shared_page_hits": st_f["shared_page_hits"],
        # from the pool's refcount ledger (peak extra references), not a
        # fork-count proxy -- reads non-zero for *any* sharing mechanism
        "shared_page_savings": st_f["shared_page_savings"],
    }
    emit("serving/shared_prefix", dt_f / n_forks * 1e6,
         f"prefill_tokens={st_f['prefill_tokens']:.0f}"
         f"(vs{st_i['prefill_tokens']:.0f});"
         f"pages={st_f['pages_allocated']:.0f}"
         f"(vs{st_i['pages_allocated']:.0f});"
         f"speedup_vs_independent={dt_i/max(dt_f, 1e-9):.2f}")

    # N *independent* submissions with the radix prefix store: no Session,
    # no fork() -- the store matches each later prompt's prefix against the
    # first request's pages and shares them copy-on-write automatically.
    # shared_page_savings comes from the pool's refcount ledger (and the
    # prefix-store hits feeding it), so it reads > 0 here even though the
    # caller never forked anything -- the reporting fix this artifact pins.
    eng_s = Engine(params, cfg, dataclasses.replace(
        scfg, prefix_cache=True, prefix_store_pages=12))
    t0 = time.perf_counter()
    for _ in range(n_forks):
        eng_s.submit(prompt, max_new_tokens=max_new)
    eng_s.run()
    dt_s = time.perf_counter() - t0
    st_s = eng_s.stats()
    assert st_s["prefix_hits"] > 0, "prefix store saw no cross-request hits"
    assert st_s["shared_page_savings"] > 0, \
        "refcount ledger shows no sharing despite prefix hits"
    assert st_s["prefill_tokens"] < st_i["prefill_tokens"], \
        "prefix store did not reduce prefill work"
    SERVING_ARTIFACT["shared_prefix"]["cross_request"] = {
        "n_requests": n_forks,
        "prefill_tokens": st_s["prefill_tokens"],
        "prefill_tokens_baseline": st_i["prefill_tokens"],
        "shared_page_hits": st_s["shared_page_hits"],
        "shared_page_savings": st_s["shared_page_savings"],
        "prefix_hits": st_s["prefix_hits"],
        "prefix_hit_tokens": st_s["prefix_hit_tokens"],
    }
    emit("serving/shared_prefix_xreq", dt_s / n_forks * 1e6,
         f"prefill_tokens={st_s['prefill_tokens']:.0f}"
         f"(vs{st_i['prefill_tokens']:.0f});"
         f"prefix_hits={st_s['prefix_hits']:.0f};"
         f"shared_page_savings={st_s['shared_page_savings']:.0f}")
    _dump_serving_artifact()


def serving_chaos():
    """Goodput under a fixed-seed fault plan vs the clean run.  The hard
    gate (bit-exact non-faulted requests, zero-cost-when-disabled, trace
    schema) lives in benchmarks/chaos_smoke.py / CI's Chaos step; this row
    records the headline resilience numbers into BENCH_serving.json."""
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.api import Engine, ServeConfig
    from repro.serving.sampler import SamplingConfig
    cfg = get_smoke_config("mamba2-2.7b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (10, 14, 18, 22)]
    plan = "alloc:nth=1;nan:rid=2;slow_step:step=4,ms=10"

    def run(fault_plan=None):
        eng = Engine(params, cfg, ServeConfig(
            backend="paged", batch=2, n_pages=17, n_slabs=5,
            sampling=SamplingConfig(temperature=0.0), fault_plan=fault_plan,
            step_budget_s=5e-3 if fault_plan else None))
        hs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        t0 = time.perf_counter()
        eng.run()
        return eng, hs, time.perf_counter() - t0

    eng_c, hs_c, dt_c = run()
    eng_f, hs_f, dt_f = run(plan)
    toks_c = sum(len(h.output) for h in hs_c)
    toks_f = sum(len(h.output) for h in hs_f)
    goodput_c = sum(1 for h in hs_c if h.status == "done") / len(hs_c)
    goodput_f = sum(1 for h in hs_f if h.status == "done") / len(hs_f)
    m_f = eng_f.obs.metrics
    injected = dict(eng_f.engine.faults.injected)
    recovered = m_f.family_total("faults_recovered_total")
    st_f = eng_f.stats()
    SERVING_ARTIFACT["chaos"] = {
        "fault_plan": plan, "seed": 0,
        "goodput_clean": goodput_c, "goodput_faulted": goodput_f,
        "tokens_per_s_clean": toks_c / max(dt_c, 1e-9),
        "tokens_per_s_faulted": toks_f / max(dt_f, 1e-9),
        "faults_injected": injected,
        "faults_recovered": recovered,
        "requests_failed": st_f["requests_failed"],
        "requests_rejected": st_f["requests_rejected"],
        "quarantines": m_f.value("quarantines_total"),
        "watchdog_trips": m_f.value("watchdog_trips_total"),
    }
    emit("serving/chaos", dt_f / max(toks_f, 1) * 1e6,
         f"goodput_clean={goodput_c:.2f};goodput_faulted={goodput_f:.2f};"
         f"injected={sum(injected.values())};recovered={recovered:.0f};"
         f"failed={st_f['requests_failed']:.0f}")
    _dump_serving_artifact()


def serving_spec():
    """Speculative decoding: self-drafted greedy serving vs plain decode.

    A repetitive prompt (the n-gram draft's best case) decodes with and
    without ``spec="ngram"``; greedy outputs must be bit-identical and the
    artifact records the schema-stable acceptance counters plus the
    analytical pimsim verify-step model at the measured acceptance rate."""
    from repro.configs import get_smoke_config
    from repro.core import pimsim as PS
    from repro.models import model as M
    from repro.serving.api import Engine, ServeConfig
    from repro.serving.sampler import SamplingConfig
    cfg = get_smoke_config("llama3.2-1b")
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    prompt = np.concatenate([base, base, base]).astype(np.int32)
    max_new = 32

    def run(spec):
        eng = Engine(params, cfg, ServeConfig(
            backend="paged", batch=2, n_pages=17, n_slabs=5,
            sampling=SamplingConfig(temperature=0.0), spec=spec, spec_k=3))
        h = eng.submit(prompt, max_new_tokens=max_new)
        t0 = time.perf_counter()
        eng.run()
        return eng, h, time.perf_counter() - t0

    eng_p, h_p, dt_p = run(None)
    eng_s, h_s, dt_s = run("ngram")
    assert h_s.output == h_p.output, \
        "speculative greedy output diverged from plain decode"
    st = eng_s.stats()
    assert st["accepted_tokens_per_step"] > 1.0, \
        "self-drafting accepted nothing on its best-case workload"
    sys_cfg = PS.SystemConfig()
    spec_m = PS.PAPER_MODELS["zamba2-7b"]
    model_speedup = (PS.spec_generation_throughput(
        spec_m, 16, 2048, 3, st["acceptance_rate"], sys_cfg, "pimba")
        / PS.generation_throughput(spec_m, 16, 2048, sys_cfg, "pimba"))
    SERVING_ARTIFACT["spec"] = {
        "draft": "ngram", "spec_k": 3,
        "proposed_tokens": st["proposed_tokens"],
        "accepted_tokens": st["accepted_tokens"],
        "acceptance_rate": st["acceptance_rate"],
        "accepted_tokens_per_step": st["accepted_tokens_per_step"],
        "greedy_bit_identical": True,
        "pimsim_speedup_at_rate": model_speedup,
    }
    emit("serving/spec", dt_s / max(len(h_s.output), 1) * 1e6,
         f"acc_per_step={st['accepted_tokens_per_step']:.2f};"
         f"rate={st['acceptance_rate']:.2f};"
         f"proposed={st['proposed_tokens']:.0f};"
         f"pimsim_speedup={model_speedup:.2f}")
    _dump_serving_artifact()


BENCHES = [fig3_latency_breakdown, fig4_swamping, fig5a_pim_designs,
           fig6_area_accuracy, fig12_generation, fig13_latency_reduction,
           fig15_latency_memory, kernel_state_update, kernel_attention,
           serving_throughput, serving_open_loop, serving_shared_prefix,
           serving_chaos, serving_spec]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for bench in BENCHES:
        bench()


if __name__ == "__main__":
    main()
