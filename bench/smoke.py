"""Smoke-size stand-ins of the benchmark's configurations and mixes, for
the CPU tests: the same harness code and traffic shapes, a model and a pool
small enough for the Pallas interpreter.

``CHECK`` holds the smoke size's own limits, set as the full-size limits
are (PERF.md) from CPU readings: see ``bench/test_control.py``."""

_SSM = {"d_state": 16, "head_dim": 16, "expand": 2, "d_conv": 4, "chunk": 16}
_SQ = {"fmt": "mx8", "rounding": "stochastic", "backend": "pallas"}

MODELS = {
    "mamba2-2.7b": {
        "name": "mamba2-smoke", "family": "ssm", "n_layers": 4,
        "d_model": 64, "n_heads": 8, "n_kv_heads": 8, "head_dim": 16,
        "d_ff": 0, "vocab_size": 512, "pattern": ["mamba2"],
        "ffn_kind": "none", "pos_emb": "none", "norm_eps": 1e-05,
        "tie_embeddings": True, "ssm": _SSM, "state_quant": _SQ,
        "param_dtype": "float32", "compute_dtype": "float32"},
}

CHECK = {"prefill_err": 1e-4, "max_gap": 0.01, "sample": 64}

_POOL = {"prefill_chunk": 64, "prefill_buckets": [16, 32, 64]}

MIXES = {
    "chat-open": {
        "rate_rps": 4.0, "lead_s": 1.0, "trace_s": 2.0,
        "prompt": {"median": 40, "sigma": 0.5, "min": 16, "max": 64},
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "pool": {"batch": 4, "n_pages": 17, "n_slabs": 5, **_POOL}},
}


def run(workload: str, seed: int = 7, seconds: float = 3.0, **kw) -> dict:
    """One run of ``workload`` at smoke size on whatever JAX finds."""
    import time
    from bench import harness
    rs = harness.resolve(harness.load_spec(), workload)
    return harness.run_cell(
        workload, seed, seconds, kw.pop("trace", False), time.perf_counter(),
        allow_cpu=True,
        config_override={"model": MODELS[rs["cell"]["config"]],
                         "check": CHECK},
        mix_override=MIXES[rs["cell"]["traffic"]], **kw)
