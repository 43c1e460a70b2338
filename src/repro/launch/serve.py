"""Production serving launcher: the Pimba system loop.

The paged, bank-aware pool with the preempting scheduler:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --smoke-size --pages 33 --requests 16 --mixed \
        --policy priority --top-p 0.95 --seed 7

It serves through the request-lifecycle facade (`repro.serving.api.Engine`):
`--stream` drives the engine open-loop and prints tokens as they are
sampled; `--turns N` runs a multi-turn session on copy-on-write prefix
sharing after the batch drains.

Weights come from --ckpt-dir (a training checkpoint) or random init.
"""
import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke-size", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="rows in the jitted decode step")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--state-format", default="mx8",
                    choices=["mx8", "int8", "fp16", "fp32"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "pallas", "jnp"],
                    help="SPU op backend; 'auto' asks the op registry for "
                         "the preferred backend capable of --state-format "
                         "in the paged layout. A concrete choice errors if "
                         "any SPU compute op the model runs (state_update / "
                         "attn_decode / mla_decode) lacks that (op, format, "
                         "backend, layout) registration; kv_append always "
                         "negotiates")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG seed for reproducible runs")
    # paged pool + scheduler
    ap.add_argument("--pages", type=int, default=33,
                    help="pool size in 128-token pages (incl. 1 scratch)")
    ap.add_argument("--slabs", type=int, default=None,
                    help="state slabs (default: 2*batch + 1)")
    ap.add_argument("--prefill-chunk", type=int, default=128,
                    help="longest full-sequence prefill; longer prompts "
                         "stream their tail through the decode batch")
    ap.add_argument("--policy", default="fcfs",
                    choices=["fcfs", "priority", "deadline"])
    ap.add_argument("--mixed", action="store_true",
                    help="mixed workload: short and long prompts")
    # request-lifecycle demos
    ap.add_argument("--stream", action="store_true",
                    help="drive the engine open-loop (step()) and print "
                         "each request's tokens as they are sampled")
    ap.add_argument("--turns", type=int, default=0,
                    help="after the batch: run a --turns-turn chat session "
                         "on copy-on-write prefix sharing")
    # observability
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="write the structured trace at exit: Chrome-trace "
                         "JSON (open in https://ui.perfetto.dev), or JSONL "
                         "if OUT ends in .jsonl")
    ap.add_argument("--metrics", default=None, metavar="OUT",
                    help="dump the metrics registry in Prometheus text "
                         "exposition format at exit ('-' for stdout)")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from repro import ops as OPS
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    from repro.serving.api import Engine, ServeConfig
    from repro.serving.sampler import SamplingConfig
    from repro.serving.scheduler import SchedulerConfig

    enable_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke_size
           else get_config(args.arch))
    if cfg.encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: nothing to serve")
    # capability lookup in the SPU op registry (replaces the old inline
    # "pallas if mx8 else jnp" heuristic): every SPU *compute* op this model
    # dispatches must support a concrete requested triple, so a bad
    # --backend fails up front; kv_append (a scatter, jnp-only by design)
    # always negotiates, as does everything under --backend auto
    requested = None if args.backend == "auto" else args.backend
    compute_kinds = sorted({e.kind for e in OPS.decode_op_plans(cfg, 1, 128)}
                           - {"kv_append"})
    try:
        resolved = [OPS.resolve_backend(kind, args.state_format, requested,
                                        layout="paged",
                                        strict=requested is not None)
                    for kind in compute_kinds]
        backend = resolved[0] if resolved else OPS.resolve_backend(
            "state_update", args.state_format, requested, layout="paged")
    except ValueError as e:
        raise SystemExit(f"--backend {args.backend}: {e}")
    cfg = cfg.with_(state_quant=OPS.StateQuantConfig(
        fmt=args.state_format, rounding="stochastic", backend=backend))

    params = M.init_model(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(args.ckpt_dir)
        restored, step = mgr.restore({"params": params, "opt_state": None})
        params = restored["params"]
        print(f"loaded checkpoint step {step}")

    sampling = SamplingConfig(temperature=args.temperature,
                              top_k=40 if args.temperature > 0 else 0,
                              top_p=args.top_p)
    scfg = ServeConfig(
        batch=args.batch,
        n_pages=args.pages,
        n_slabs=args.slabs,
        prefill_chunk=args.prefill_chunk,
        sampling=sampling,
        scheduler=SchedulerConfig(policy=args.policy),
        seed=args.seed)
    eng = Engine(params, cfg, scfg)

    rng = np.random.default_rng(args.seed)
    handles = []
    for i in range(args.requests):
        if args.mixed:
            # alternate short prompts with multi-page long ones
            n = 8 + i % 24 if i % 3 else 130 + 16 * (i % 4)
        else:
            n = 8 + i % 24
        handles.append(eng.submit(
            rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=args.max_new,
            priority=i % 3 if args.policy == "priority" else 0,
            deadline=(time.time() + 1 + i % 5
                      if args.policy == "deadline" else None)))
    t0 = time.perf_counter()
    # score the page map of the first admitted batch with the PIM bank
    # model (computed on demand: no decode step accounts bank traffic)
    eng.step()
    bank = eng.engine.bank_report()
    if args.stream:
        # open-loop: one step at a time, tokens printed as they surface
        running = True
        while running:
            running = eng.step()
            for h in handles:
                got = h.new_tokens()
                if got:
                    print(f"  req {h.rid} [{h.status}] += {got}")
        done = [h.request for h in handles]
    else:
        done = eng.run()
    stats = eng.stats()
    print(f"{len(done)} requests, {stats['tokens']:.0f} tokens, "
          f"{stats['tokens_per_s']:.1f} tok/s "
          f"(wall {time.perf_counter()-t0:.1f}s, state={args.state_format}, "
          f"backend={backend})")
    print(f"  steps: p99={stats['p99_step_s']*1e3:.1f}ms "
          f"p99_nocompile={stats['p99_step_nocompile_s']*1e3:.1f}ms "
          f"({int(stats['compile_steps'])} compile steps, "
          f"{int(stats['recompiles'])} jit compiles)")
    traffic = {k.split("/", 1)[1]: v for k, v in stats.items()
               if k.startswith("op_traffic_bytes/")}
    if traffic:
        total = sum(traffic.values())
        parts = " ".join(f"{k}={v/1e6:.1f}MB" for k, v in traffic.items())
        print(f"  spu op traffic: {parts} (total {total/1e6:.1f}MB)")
    for k in ("mean_ttft_s", "p50_ttft_s", "p99_ttft_s",
              "p50_tok_latency_s", "p99_tok_latency_s"):
        if k in stats:
            print(f"  {k}={stats[k]*1e3:.1f}ms", end="")
    print()
    print(f"  occupancy={stats['occupancy']:.2f} "
          f"fragmentation={stats['fragmentation']:.2f} "
          f"preemptions={int(stats['preemptions'])}")
    print(f"  pimsim page-map (first batch): "
          f"step={bank['t_real_s']*1e6:.2f}us "
          f"ideal={bank['t_ideal_s']*1e6:.2f}us "
          f"conflict_factor={bank['conflict_factor']:.2f} "
          f"bank_imbalance={bank['imbalance']:.2f}")

    if args.turns:
        print(f"-- {args.turns}-turn session (copy-on-write prefix "
              "sharing; turn N skips re-prefilling the history) --")
        chat = eng.session()
        before = eng.stats()["prefill_tokens"]
        for t in range(args.turns):
            turn = rng.integers(0, cfg.vocab_size, 8 + t).astype(np.int32)
            h = chat.send(turn, max_new_tokens=args.max_new)
            print(f"  turn {t}: sent {len(turn)} tokens -> "
                  f"{list(h)}")
        after = eng.stats()["prefill_tokens"]
        chat.close()
        print(f"  session ingested {after - before:.0f} fresh tokens "
              f"({eng.stats()['shared_page_hits']:.0f} shared-page hits; "
              "an unshared engine would re-prefill the whole history "
              "every turn)")

    if args.trace:
        eng.save_trace(args.trace)
        counts = eng.obs.recompiles.counts()
        print(f"trace: {len(eng.obs.tracer.events())} events -> "
              f"{args.trace} (jit compiles: "
              + (" ".join(f"{k}={v}" for k, v in sorted(counts.items()))
                 or "none") + ")")
    if args.metrics:
        text = eng.prometheus_text()
        if args.metrics == "-":
            sys.stdout.write(text)
        else:
            with open(args.metrics, "w") as f:
                f.write(text)
            print(f"metrics: {args.metrics}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
