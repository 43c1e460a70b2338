"""16-token paged serving smoke (CI tier 2).

Runs four equal-length prompts, four new tokens each, through the paged
engine's block-table-native decode path, and fails if the decode step
compiled more than ``--max-decode-recompiles`` times: the workload has a
single decode shape, so more means a silent retrace crept in.

    PYTHONPATH=src python benchmarks/paged_smoke.py --max-decode-recompiles 1
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-decode-recompiles", type=int, default=1,
                    help="fail if the paged pool's decode step compiled "
                         "more than this many times over the run (the "
                         "workload is shaped for a single decode shape; "
                         "more means a silent retrace crept in)")
    ap.add_argument("--arch", default="mamba2-2.7b")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.api import ServeConfig
    from repro.serving.engine import PagedServingEngine, Request

    cfg = get_smoke_config(args.arch)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    # equal-length prompts: one prefill compile, one decode shape
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(4)]

    paged = PagedServingEngine(params, cfg, ServeConfig(
        batch=4, n_pages=9, n_slabs=9, prefill_chunk=128))
    for i, p in enumerate(prompts):
        paged.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    paged.run()
    p_stats = paged.stats()

    # one source of truth for wrapped jit sites: the module-level registry
    # in repro.obs.recompile (the jit-hazard linter reads the same one)
    from repro.obs import recompile as RC
    site_compiles = RC.site_compile_counts()
    decode_compiles = site_compiles.get("pool.decode", 0)
    print(f"paged:   {p_stats['tokens']} tokens, "
          f"{p_stats['tokens_per_s']:.2f} tok/s, "
          f"gather_bytes={p_stats['gather_bytes']:.0f}")
    print(f"paged decode compiles={decode_compiles} "
          f"(budget {args.max_decode_recompiles}); "
          f"jit sites: " + " ".join(
              f"{k}={v}" for k, v in sorted(site_compiles.items())))
    if decode_compiles > args.max_decode_recompiles:
        for ev in paged.obs.recompiles.events:
            if ev.fn == "pool.decode" and not ev.is_warmup:
                print(f"  retrace: {ev.changed}", file=sys.stderr)
        print("FAIL: paged decode step retraced beyond the pinned budget",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
