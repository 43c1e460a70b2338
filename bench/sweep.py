#!/usr/bin/env python3
"""Find the rate an open-loop cell sustains: one engine, one window per
offered rate, in one process.

    python3 bench/sweep.py --workload mamba2-chat-open --seed 5 \
        --seconds 30 --rates 0.5 1 1.5 2 3

For each rate it prints one JSON line: the offered and completed request
rates, tokens/s, ``ttft_p90_s``, ``itl_p99_ms``, how many requests were
still waiting when the window closed and the p90 of their queued time.
Requests left at the close are aborted before the next rate.  The cell's
mix file keeps the rate the benchmark runs at; this only reads the knee.
Needs a TPU.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, traffic, yardstick  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    harness.enable_cache(jax)
    if jax.devices()[0].platform != "tpu":
        harness.say("bench: needs a TPU")
        return 2
    rs = harness.resolve(harness.load_spec(), args.workload)
    mix, model = rs["mix"], rs["config"]["model"]
    weights = rs["reference"].make_weights(model, args.seed)
    eng = harness.build_engine(weights, harness.program_config(model),
                               mix["pool"], args.seed)
    harness.warm_up(eng, mix, model["vocab_size"])
    annotate = harness._annotator(jax, False)
    for rate in args.rates:
        m = {**mix, "rate_rps": rate}
        reqs = traffic.requests(m, args.seed, model["vocab_size"],
                                args.seconds)
        t0 = time.perf_counter()
        d, (w0, w1) = harness.drive(eng, m, reqs, args.seconds, annotate)
        e2e = harness.end_to_end(d, (w0, w1))
        toks = sum(1 for r in d.records for t in r.times if w0 <= t <= w1)
        done = sum(1 for r in d.records
                   if r.done is not None and w0 <= r.done <= w1)
        waiting = sum(1 for r in d.records if r.done is None)
        queued = []                 # as ``queue_wait_p90_s.chat`` reads it
        for r in d.records:
            rec = eng.lifecycle(r.handle) if w0 <= r.due < w1 else None
            span = next((x for x in rec.spans if x.phase == "queued"),
                        None) if rec is not None else None
            if span is not None:
                end = span.t1 if span.t1 is not None else w1
                queued.append(min(end, w1) - span.t0)
        for r in list(d.live):
            r.handle.abort()
        eng.run()
        print(json.dumps({
            "rate_rps": rate, "completed_rps": done / (w1 - w0),
            "output_tok_s": toks / (w1 - w0),
            "ttft_p90_s": e2e["ttft_p90_s"], "itl_p99_ms": e2e["itl_p99_ms"],
            "due": e2e["_ttft_n"], "waiting_at_close": waiting,
            "queue_p90_s": (yardstick.percentile(queued, 90)
                            if queued else None),
            "compiles": eng.obs.recompiles.n_events,
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
