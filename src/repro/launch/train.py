"""Production training launcher.

On a real fleet each host runs this under `jax.distributed.initialize()`
(the mesh helpers below then see all pods' devices); in this container it
runs the same code on the local device(s), optionally with a host-platform
mesh for rehearsal.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 50 --seq-len 256 --global-batch 8 --ckpt-dir /tmp/ckpt

Fault tolerance: atomic checkpoints every --ckpt-every steps, auto-resume
from the newest valid checkpoint, step-indexed data order (restart-stable),
straggler watchdog in the loop.
"""
import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke-size", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="'data x model', e.g. 2x4 (needs that many devices)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="fake host devices for mesh rehearsal (sets XLA_FLAGS)")
    args = ap.parse_args(argv)

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    import jax
    import numpy as np
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config, get_smoke_config
    from repro.data.pipeline import make_batch_fn
    from repro.dist import sharding as SH
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    from repro.train import optimizer as O
    from repro.train.train_loop import LoopConfig, make_train_step, train_loop

    enable_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke_size
           else get_config(args.arch))
    opt = O.OptimizerConfig(lr=args.lr, total_steps=args.steps,
                            warmup_steps=max(args.steps // 20, 1))

    par = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
        from repro.launch.mesh import make_local_parallel
        par = make_local_parallel(data=d, model=m)

    params = M.init_model(jax.random.PRNGKey(0), cfg)
    opt_state = O.init_opt_state(params, opt)
    n = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n/1e6:.1f}M mesh={args.mesh or 'single'}")

    step_fn = make_train_step(cfg, opt, par=par, grad_accum=args.grad_accum)
    if par is not None:
        p_shard = SH.param_shardings(params, cfg, par)
        o_shard = SH.opt_state_shardings(opt_state, p_shard, par)
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, o_shard)
        step_fn = jax.jit(step_fn, in_shardings=(p_shard, o_shard, None),
                          donate_argnums=(0, 1))
        ctx = par.mesh
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        import contextlib
        ctx = contextlib.nullcontext()

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr is not None and mgr.latest_step() is not None:
        restored, start = mgr.restore({"params": params,
                                       "opt_state": opt_state})
        params, opt_state = restored["params"], restored["opt_state"]
        print(f"auto-resumed from step {start}")

    batch_fn = make_batch_fn(cfg, args.seq_len, args.global_batch)
    with ctx:
        params, opt_state, hist = train_loop(
            step_fn, params, opt_state, batch_fn,
            LoopConfig(total_steps=args.steps, log_every=10,
                       checkpoint_every=args.ckpt_every),
            checkpoint_mgr=mgr, start_step=start)
    print(f"done: loss {hist[0]:.3f} -> {hist[-1]:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
