"""The trace reduction on a recorded trace: one zamba2-2.7b decode call of
the chat cell on a TPU v5 lite (``testdata/trace_decode_step.json``)."""
import json
import math
import os

import pytest

from bench import tracecut as T

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "testdata", "trace_decode_step.json")) as f:
        t = json.load(f)
    return [{"plane": t["planes"][p], "line": t["lines"][ln], "name": name,
             "t0": a * 1e-9, "t1": (a + d) * 1e-9}
            for p, ln, name, a, d in t["events"]]


def test_busy_and_idle(events):
    r = T.reduce(events)
    assert r["window_s"] == pytest.approx(0.209660716, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.178710234, abs=1e-9)
    assert r["idle_share"] == pytest.approx(1 - 0.178710234 / 0.209660716)
    # the same busy time on a 1 us grid, counted independently
    grid = set()
    for e in events:
        if e["line"] == T.OPS_LINE:
            grid.update(range(int(e["t0"] * 1e6), math.ceil(e["t1"] * 1e6)))
    assert r["busy_s"] == pytest.approx(len(grid) * 1e-6, abs=2e-5)


def test_kernels_and_programs(events):
    r = T.reduce(events)
    k = r["kernels"]
    assert {n: v["calls"] for n, v in k.items()} == {
        "state_update": 54, "kv_append": 9, "attn_decode": 9}
    assert k["state_update"]["seconds"] == pytest.approx(0.08659313, abs=1e-9)
    assert k["attn_decode"]["seconds"] == pytest.approx(0.033054253, abs=1e-9)
    assert r["programs"]["decode_impl"] == {
        "seconds": pytest.approx(0.202660716, abs=1e-9), "calls": 1}
    window = (0.0, r["window_s"])
    calls = T.program_calls(events, window)
    assert [c[0] for c in calls].count("convert_element_type") == 3
    (name, a, b), = T.program_calls(events, window, "decode_impl")
    assert b - a == pytest.approx(0.202660716, abs=1e-9)


def test_breakdown(events):
    b = T.reduce(events)["breakdown"]
    ops = dict(b["device_ops"])
    # the layer loop's own time excludes the kernels nested in it
    assert ops["while"] == pytest.approx(0.059007129, abs=1e-9)
    assert [n for n, _ in b["device_ops"][:3]] == [
        "spu_state_update", "while", "spu_attn_decode"]
    assert dict(b["idle_gaps"]) == {
        "step": pytest.approx(0.02964647, abs=1e-9),
        "outside": pytest.approx(0.001304012, abs=1e-9)}


def test_self_times_add_up_to_busy(events):
    r = T.reduce(events)
    assert sum(v for _, v in r["breakdown"]["device_ops"]) == pytest.approx(
        r["busy_s"], abs=1e-9)
    # a nested op that ends a rounding past its loop still counts inside it
    ev = lambda n, a, b: {"name": n, "t0": a, "t1": b}
    got = dict(T._self_times([ev("while.1", 0.0, 1.0),
                              ev("fusion.2", 0.1, 0.5),
                              ev("copy.3.remat", 0.6, 1.0 + 1e-9),
                              ev("fusion.4", 1.5, 2.0)]))
    assert got["while"] == pytest.approx(0.2)
    assert got["copy"] == pytest.approx(0.4)
    assert got["fusion"] == pytest.approx(0.5)


def test_names():
    assert T.short_name("%fusion.3 = f32[2]{0} fusion(%p)") == "fusion.3"
    assert T.kernel_kind("spu_state_update.27") == "state_update"
    assert T.kernel_kind("spu_attn_decode.11.clone") == "attn_decode"
    assert T.kernel_kind("get-tuple-element.5") is None
