"""The one general traffic generator: a mix file of parameters -> requests.

A mix (``bench/traffic/<name>.json``) gives an open loop's fixed rate in
req/s and its lead-in of ``lead_s`` seconds, lognormal prompt and output
lengths (median, sigma, clip range), and the engine's pool.

Every seed serves the same set of sizes and arrival gaps: they are drawn
once from the mix's own ``base_seed``; ``--seed`` only permutes them and
draws the token ids.  The lead-in and the window are two sets, each
permuted within itself, so every seed's window holds the same
requests.  Runs with different seeds then do the same work in a different
order, and their spread measures the system, not the draw.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    idx: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due: float                  # seconds after the loop starts


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def fixed_sets(mix: dict, seconds: float):
    """[(prompt lengths, output lengths, arrival gaps)] of the mix's sets:
    the lead-in's and the window's, each a Poisson draw of ``rate * span``
    requests given their count: n + 1 exponential gaps scaled to fill the
    span, of which the first n are kept, so every request is due inside its
    span."""
    rng = np.random.default_rng(mix["base_seed"])
    out = []
    for span in (mix["lead_s"], seconds):
        n = max(1, int(round(mix["rate_rps"] * span)))
        prompts = _lognormal(rng, mix["prompt"], n)
        outputs = _lognormal(rng, mix["output"], n)
        gaps = rng.exponential(1.0 / mix["rate_rps"], n + 1)
        out.append((prompts, outputs, (gaps * span / gaps.sum())[:n]))
    return out


def requests(mix: dict, seed: int, vocab: int, seconds: float) -> List[Req]:
    """The mix's requests for ``seed`` and a window of ``seconds``, in
    sending order."""
    rng = np.random.default_rng(int(seed))
    out: List[Req] = []
    t = 0.0
    for prompts, outputs, gaps in fixed_sets(mix, seconds):
        prompts = prompts[rng.permutation(len(prompts))]
        outputs = outputs[rng.permutation(len(outputs))]
        due = t + np.cumsum(gaps[rng.permutation(len(gaps))])
        t = mix["lead_s"]
        out += [Req(len(out) + i, rng.integers(0, vocab, int(p))
                    .astype(np.int32), int(o), float(d))
                for i, (p, o, d) in enumerate(zip(prompts, outputs, due))]
    return out
