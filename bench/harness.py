"""One run of one cell: build, warm up, drive the traffic, check, report.

The harness is driven by ``BENCHMARK.json`` and finds everything by name:

* ``configs[].file`` -- the configuration's sizes (JSON); its plain
  reference and weight maker sit beside it as ``<file stem>.py``;
* ``bench/traffic/<traffic>.json`` -- the mix, read by ``traffic.py``;
* ``bench/metrics/<metric name>.py`` -- one reader per per-layer metric,
  ``read(ctx) -> float | None``;
* ``bench/peaks.json`` -- the chip's peaks, by ``device_kind``.

Adding a configuration, a mix or a per-layer metric is adding files and
entries; no code here names one.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import tracecut, traffic, yardstick

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoDevice(Exception):
    pass


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    ref_path = os.path.join(root, os.path.splitext(conf["file"])[0] + ".py")
    applies = lambda m: workload in m.get("workloads", [workload])
    e2e = [m for m in spec["end_to_end"] if applies(m)]
    layers = [m for m in spec["per_layer"] if applies(m)]
    return {"cell": cell, "config": config, "mix": mix,
            "reference": load_module(ref_path, "bench_ref_" + conf["name"]
                                     .replace("-", "_").replace(".", "_")),
            "end_to_end": e2e, "per_layer": layers,
            "readers": {m["name"]: os.path.join(root, "bench", "metrics",
                                                m["name"] + ".py")
                        for m in layers}}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def program_config(model: dict):
    """The program's ModelConfig from the configuration file's ``model``."""
    from repro.models.config import ModelConfig, SSMConfig
    from repro.ops.base import StateQuantConfig
    kw = dict(model)
    kw["pattern"] = tuple(kw["pattern"])
    kw["ssm"] = SSMConfig(**kw["ssm"])
    kw["state_quant"] = StateQuantConfig(**kw["state_quant"])
    return ModelConfig(**kw)


def build_engine(params, cfg, pool: dict, seed: int):
    from repro import ops as OPS
    from repro.serving.api import Engine, ServeConfig
    from repro.serving.sampler import SamplingConfig
    sq = cfg.state_quant
    for kind in ("state_update", "kv_append", "attn_decode"):
        got = OPS.resolve_backend(kind, sq.fmt, sq.backend, layout="paged",
                                  strict=True)
        assert got == sq.backend, f"{kind} resolved to {got}"
    scfg = ServeConfig(backend="paged", batch=pool["batch"],
                       n_pages=pool["n_pages"], n_slabs=pool["n_slabs"],
                       prefill_chunk=pool["prefill_chunk"],
                       prefill_buckets=tuple(pool["prefill_buckets"]),
                       sampling=SamplingConfig(temperature=0.0),
                       seed=seed & 0x7FFFFFFF)
    return Engine(params, cfg, scfg)


# ---------------------------------------------------------------------------
# driving the engine
# ---------------------------------------------------------------------------

class Record:
    """What the client sees of one request, on the host clock."""
    __slots__ = ("req", "handle", "due", "sent", "times", "status", "done")

    def __init__(self, req, handle, due, sent):
        self.req, self.handle, self.due, self.sent = req, handle, due, sent
        self.times: List[float] = []
        self.status = "queued"
        self.done: Optional[float] = None


class Client:
    """Submits, steps and collects tokens; every call into the engine sits
    in a ``bench.<what>`` trace annotation."""

    def __init__(self, eng, annotate):
        self.eng = eng
        self.ann = annotate
        self.live: List[Record] = []
        self.records: List[Record] = []
        self.steps: List[tuple] = []    # (t0, t1, rows) of each step
        self.log_rows = False
        self.compiles_at_w0 = None      # jit compiles when the window opened

    def submit(self, req, due: float) -> Record:
        with self.ann("bench.submit"):
            h = self.eng.submit(req.prompt, max_new_tokens=req.max_new)
        r = Record(req, h, due, time.perf_counter())
        self.live.append(r)
        self.records.append(r)
        return r

    def step(self) -> List[Record]:
        t0 = time.perf_counter()
        with self.ann("bench.step"):
            self.eng.step()
        with self.ann("bench.read"):
            t = time.perf_counter()
            finished = []
            for r in self.live:
                new = r.handle.new_tokens()
                if new:
                    r.times.extend([t] * len(new))
                if r.handle.finished:
                    r.status, r.done = r.handle.status, t
                    finished.append(r)
            if finished:
                self.live = [r for r in self.live if r.done is None]
            if self.log_rows:
                self.steps.append((t0, t, self._rows(finished)))
        return finished

    def _rows(self, finished) -> Optional[list]:
        """(context length incl. the appended token, page ids) of the rows
        the step decoded: the requests still running, and those that
        finished in it.  The engine's rows are its own to rename: None
        when they cannot be read, and the readers then find nothing."""
        core = self.eng.engine
        try:
            rows = [(a.length, list(core.pool.page_table.get(rid, [])))
                    for rid, a in core.active.items()]
        except AttributeError:
            return None
        rows += [(len(r.req.prompt) + len(r.times) - 1, [])
                 for r in finished]
        return rows


def warm_up(eng, mix: dict, vocab: int) -> None:
    """Compile every shape the mix uses: one request at each prefill bucket,
    one after the other.  The longest bucket's request decodes onto a fresh
    page, so every decode block-table width up to the widest a prefilled
    prompt reaches runs too."""
    rng = np.random.default_rng(0)
    for n in mix["pool"]["prefill_buckets"]:
        eng.submit(rng.integers(0, vocab, n).astype(np.int32),
                   max_new_tokens=2)
        eng.run()


def drive(eng, mix: dict, reqs, seconds: float, annotate, trace_hook=None):
    """Run the mix's lead-in and then the measured window.

    Open loop: request i is due at ``t0 + due_i``; it is sent at the first
    loop turn at or after that; the window opens ``lead_s`` after ``t0``.
    Returns the client and the window (w0, w1) on the host clock."""
    d = Client(eng, annotate)
    t0 = time.perf_counter()
    nxt = 0
    w0 = t0 + mix["lead_s"]
    w1 = w0 + seconds
    # a traced run profiles the last ``trace_s`` seconds of its window
    t_trace = w1 - min(mix.get("trace_s", seconds), seconds)
    traced = False
    while True:
        now = time.perf_counter()
        if now >= w1:
            break
        if d.compiles_at_w0 is None and now >= w0:
            d.compiles_at_w0 = eng.obs.recompiles.n_events
        if trace_hook is not None and not traced and now >= t_trace:
            trace_hook()
            d.log_rows = traced = True
        while nxt < len(reqs) and t0 + reqs[nxt].due <= now:
            d.submit(reqs[nxt], t0 + reqs[nxt].due)
            nxt += 1
        if eng.has_work():
            d.step()
        else:
            wake = min(t0 + reqs[nxt].due if nxt < len(reqs) else w1, w1)
            time.sleep(max(0.0, wake - time.perf_counter()))
    return d, (w0, w1)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def end_to_end(d: Client, win) -> Dict[str, float]:
    w0, w1 = win
    ttft = []
    for r in d.records:
        if not w0 <= r.due < w1:
            continue
        first = r.times[0] if r.times else None
        if r.status in ("failed", "rejected", "aborted") or first is None \
                or first > w1:
            ttft.append(w1 - r.due)
        else:
            ttft.append(first - r.due)
    gaps = [b - a for r in d.records for a, b in zip(r.times, r.times[1:])
            if w0 <= a and b <= w1]
    return {"ttft_p90_s": yardstick.percentile(ttft, 90),
            "itl_p99_ms": 1e3 * yardstick.percentile(gaps, 99),
            "_ttft_n": len(ttft), "_itl_n": len(gaps)}


class PrefillTap:
    """Keeps the logits of every prefill the engine runs, on the device and
    unread until the window has closed, by the request they were for: the
    prefill returns the logits of its last position, and the engine then
    inserts that request's caches."""

    def __init__(self, core):
        self._prefill, self._insert = core._prefill, core.pool.insert_prefill
        self._last = None
        self.logits: Dict[int, tuple] = {}     # rid -> (length, logits)
        core._prefill = self.prefill
        core.pool.insert_prefill = self.insert

    def prefill(self, params, batch):
        out = self._prefill(params, batch=batch)
        self._last = (batch["tokens"].shape[1], out[0])
        return out

    def insert(self, rid, row_caches):
        self.logits[rid], self._last = self._last, None
        return self._insert(rid, row_caches)


#: the numbers compared, each against the limit of that name in the
#: configuration's ``check``
LIMITED = ("prefill_err", "max_gap")


def check(ref_mod, weights, model: dict, d: Client, win, cfg_check: dict,
          mix: dict, seed: int, prefills: Dict[int, tuple], controls):
    """A seeded sample of the requests that finished in the window, the
    longest among them, against the plain reference: their prefill's
    logits and every served token (``bench/model.py``)."""
    done = [r for r in d.records
            if r.status == "done" and r.done is not None and r.done <= win[1]
            and r.req.max_new > 0]
    if not done:
        return None
    rng = np.random.default_rng(int(seed) + 1)
    longest = max(done, key=lambda r: len(r.req.prompt) + len(r.times))
    rest = [r for r in done if r is not longest]
    k = min(cfg_check["sample"] - 1, len(rest))
    pick = [longest] + [rest[i] for i in rng.choice(len(rest), k,
                                                    replace=False)]
    reqs = []
    for r in pick:
        s0, logits = prefills.get(r.handle.rid, (1, None))
        if logits is not None:
            logits = np.asarray(logits, np.float32).reshape(-1)
        reqs.append((r.req.prompt, np.asarray(r.handle.output, np.int32),
                     s0, logits))
    pad = max(len(p) + len(o) for p, o, _, _ in reqs)
    pad = max(pad, mix["prompt"]["max"] + mix["output"]["max"])
    return ref_mod.served_readings(weights, model, reqs, pad, controls)


def decide(readings: Optional[dict], n: int, cfg_check: dict):
    """``correct`` and the numbers beside their limits, for the program or
    for a control standing in for it."""
    chk = {k: {"value": None if readings is None else readings[k],
               "limit": cfg_check[k]} for k in LIMITED}
    chk["compared_tokens"] = {"value": n, "limit": 1}
    correct = n >= 1 and all(v["value"] is not None
                             and v["value"] <= v["limit"]
                             for k, v in chk.items() if k in LIMITED)
    return bool(correct), chk


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, root: str = ROOT, allow_cpu: bool = False,
             config_override: Optional[dict] = None,
             mix_override: Optional[dict] = None,
             controls: Sequence[str] = ()) -> dict:
    """One run; returns the result line's object.  With ``controls``
    (``bench/model.CONTROLS``), each control also stands in for the program
    in the comparison, and the line gets its ``correct`` under
    ``controls``."""
    spec = load_spec(root)
    rs = resolve(spec, workload, root)
    cell, config, mix = rs["cell"], rs["config"], rs["mix"]
    if mix_override:
        mix = {**mix, **mix_override}
    config = {**config, **(config_override or {})}
    model = config["model"]

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not allow_cpu:
        raise NoDevice(f"needs a TPU, JAX found {platform!r}")
    if len(devs) < cell["chips"]:
        raise NoDevice(f"cell {workload} needs {cell['chips']} chips, JAX "
                       f"found {len(devs)}")
    events = CompileEvents.get(jax)

    sys.path.insert(0, os.path.join(root, "src"))
    cfg = program_config(model)
    ref = rs["reference"]
    weights = ref.make_weights(model, seed)
    jax.block_until_ready(weights)
    t_weights = time.perf_counter()
    eng = build_engine(weights, cfg, mix["pool"], seed)
    tap = PrefillTap(eng.engine)
    warm_up(eng, mix, model["vocab_size"])
    tap.logits.clear()
    t_warm = time.perf_counter()
    compiles_warm = eng.obs.recompiles.n_events
    sites_warm = dict(eng.obs.recompiles.counts())

    reqs = traffic.requests(mix, seed, model["vocab_size"], seconds)

    annotate = _annotator(jax, trace)
    tr = {}

    def trace_hook():
        os.makedirs(TRACE_DIR, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans are bench.<what>
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        tr["ann"] = jax.profiler.TraceAnnotation("bench.window")
        tr["perf0"] = time.perf_counter()
        tr["ann"].__enter__()

    d, win = drive(eng, mix, reqs, seconds, annotate,
                   trace_hook if trace else None)
    setup_s = win[0] - t_start
    if "ann" in tr:
        tr["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles_window = eng.obs.recompiles.n_events - d.compiles_at_w0
    sites_window = {k: v - sites_warm.get(k, 0)
                    for k, v in eng.obs.recompiles.counts().items()
                    if v != sites_warm.get(k, 0)}
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dv in devs)

    e2e = end_to_end(d, win)
    due = [r for r in d.records if win[0] <= r.due < win[1]]
    failed = sum(1 for r in due
                 if r.status in ("failed", "rejected", "aborted",
                                 "truncated"))
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}

    metrics: Dict[str, dict] = {}
    breakdown = None
    if not trace:
        vals = {**e2e, "setup_s": setup_s}
        for m in rs["end_to_end"]:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    else:
        evs = tracecut.load_events(TRACE_DIR)
        red = tracecut.reduce(evs)
        ann = [e for e in evs if e["name"] == "bench.window"]
        # no peaks off the chip; an unknown chip is an error
        pk = (yardstick.peaks(devs[0].device_kind) if platform == "tpu"
              else None)
        # what a per-layer metric reader may read
        ctx = SimpleNamespace(
            client=d, window=win, engine=eng, model=model, mix=mix,
            trace=red, events=evs, peaks=pk, yardstick=yardstick,
            tracecut=tracecut,
            trace_window=(ann[0]["t0"], ann[0]["t1"]) if ann else None,
            trace_offset=ann[0]["t0"] - tr["perf0"] if ann else None)
        for m in rs["per_layer"]:
            v = load_module(rs["readers"][m["name"]],
                            "bench_metric_" + m["name"].replace(".", "_")
                            ).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = red["breakdown"]
        _clear_trace()
        del ctx                     # it holds the engine

    say(f"setup: weights {t_weights - t_start:.2f} s, engine + warm-up "
        f"{t_warm - t_weights:.2f} s, lead-in {win[0] - t_warm:.2f} s; "
        f"compile events {events.summary()}; jit compiles in warm-up "
        f"{compiles_warm}, after it {sites_window}, in the window "
        f"{compiles_window}")
    say(f"window: {len(due)} requests, {e2e['_ttft_n']} TTFT "
        f"samples, {e2e['_itl_n']} token gaps, "
        f"{sum(len(r.times) for r in d.records)} tokens in all")

    # the reference runs once the program's state is gone
    prefills = tap.logits
    del eng, tap
    d.eng = None
    for r in d.records:
        r.handle = _Frozen(r.handle)
    gc.collect()
    got = check(ref, weights, model, d, win, config["check"], mix, seed,
                prefills, controls)
    sides, n = got if got is not None else ({}, 0)
    correct, chk = decide(sides.get("program"), n, config["check"])
    out = {"correct": correct, "attempted": len(due), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if controls:
        out["controls"] = {}
        for c in controls:
            ok, cchk = decide(sides.get(c), n, config["check"])
            out["controls"][c] = {"correct": ok, "check": cchk}
            for k in LIMITED:
                say(f"control {c} {k}: {cchk[k]['value']} (limit "
                    f"{cchk[k]['limit']}); correct {ok}")
    out["check"] = chk
    for k, v in chk.items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    return out


class _Frozen:
    """A finished handle's output, kept after the engine is freed."""

    def __init__(self, h):
        self.rid = h.rid
        self.output = h.output
        self.status = h.status


class CompileEvents:
    """JAX's compile and persistent-cache events: seconds and counts."""
    _one = None

    @classmethod
    def get(cls, jax):
        if cls._one is None:
            cls._one = cls(jax)
        cls._one.secs, cls._one.counts = {}, {}
        return cls._one

    def __init__(self, jax):
        self.secs: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_):
        if "compil" in event:
            self.secs[event] = self.secs.get(event, 0.0) + duration

    def _ev(self, event, **_):
        if "cache" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def summary(self) -> dict:
        out = {k.rsplit("/", 1)[-1]: round(v, 3) for k, v in self.secs.items()}
        out.update({k.rsplit("/", 1)[-1]: v for k, v in self.counts.items()})
        return out


def _annotator(jax, on: bool):
    import contextlib
    if not on:
        return lambda name: contextlib.nullcontext()
    return jax.profiler.TraceAnnotation


def _clear_trace() -> None:
    import shutil
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


def enable_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is written, however short its compile."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        say(f"bench: the program (src/repro) is not in {ROOT}")
        return 2
    import jax
    enable_cache(jax)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except NoDevice as e:
        say(f"bench: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0
