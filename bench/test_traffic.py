"""The traffic generator: every seed serves the same sets, reordered."""
import json
import os

import numpy as np

from bench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_loop_same_window_every_seed():
    mix = _mix("chat-open")
    lead, secs = mix["lead_s"], 51.0
    a = traffic.requests(mix, 1, 32000, secs)
    b = traffic.requests(mix, 2 ** 31 + 12345, 32000, secs)
    n_lead = round(mix["rate_rps"] * lead)
    assert len(a) == len(b) == n_lead + round(mix["rate_rps"] * secs)
    for part in (slice(0, n_lead), slice(n_lead, None)):
        for key in (lambda r: len(r.prompt), lambda r: r.max_new):
            assert sorted(map(key, a[part])) == sorted(map(key, b[part]))
            assert list(map(key, a[part])) != list(map(key, b[part]))
    for reqs in (a, b):
        assert all(0 < r.due < lead for r in reqs[:n_lead])
        assert all(lead < r.due < lead + secs for r in reqs[n_lead:])
        assert all(x.due <= y.due for x, y in zip(reqs, reqs[1:]))
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)


def test_same_seed_same_requests():
    mix = _mix("chat-open")
    a = traffic.requests(mix, 7, 32000, 51.0)
    b = traffic.requests(mix, 7, 32000, 51.0)
    assert all(x.due == y.due for x, y in zip(a, b))
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    assert all(lo <= r.max_new <= hi for r in a)
