#!/usr/bin/env python3
"""Read the numbers that set a cell's limits: the program's and each
control's, over several seeds in one process.

    python3 bench/readings.py --workload <name> --seeds 11 12 13 \
        --seconds 20 [--controls high bf16 int4]

Each seed is a whole run of the cell (weights, engine, traffic, window) at
its own size and load.  After the window the sampled requests' prefill
logits and served tokens are compared with the float32 reference, and each
control (``bench/model.CONTROLS``: the reference one precision below what
the configuration states) stands in for the program on the same prompts and
tokens; the harness's own decision gives its ``correct``.  One JSON line per
seed on stdout.  Needs a TPU.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", nargs="*", default=["high", "bf16", "int4"],
                    choices=["high", "bf16", "int4"])
    args = ap.parse_args()
    import jax
    harness.enable_cache(jax)
    for seed in args.seeds:
        t0 = time.perf_counter()
        try:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t0, controls=args.controls)
        except harness.NoDevice as e:
            harness.say(f"bench: {e}")
            return 2
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "metrics": out["metrics"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"],
                          "controls": out.get("controls", {}),
                          "check": out["check"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
