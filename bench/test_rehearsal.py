"""CPU rehearsal: the cell's mix through the same harness code at smoke size;
a measured run without a chip fails; the harness finds a new mix, config
and per-layer metric from new files and entries alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload,e2e", [
    ("mamba2-chat-open", {"ttft_p90_s", "itl_p99_ms", "setup_s"}),
])
def test_rehearsal_last_line(workload, e2e):
    out = smoke.run(workload)
    assert KEYS <= set(out) and list(out)[-1] == "check"
    assert set(out["metrics"]) == e2e
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["check"]["compared_tokens"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


@pytest.mark.parametrize("workload", ["mamba2-chat-open"])
def test_rehearsal_traced(workload):
    """The traced run reads only per-layer metrics; on the CPU there is no
    device plane, so the device-trace readers find nothing to read."""
    out = smoke.run(workload, trace=True)
    spec = harness.load_spec()
    layer = {m["name"]: m for m in spec["per_layer"]
             if workload in m["workloads"]}
    assert out["metrics"] and set(out["metrics"]) <= set(layer)
    assert all(layer[n]["source"] != "device_trace" for n in out["metrics"])
    assert "busy_s" not in out["device"] and out["correct"] is True


def _run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "mamba2-chat-open", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_measured_run_without_a_chip_exits_nonzero():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_new_files_and_entries_are_found_by_name(tmp_path):
    """A new configuration, mix and per-layer metric: files and entries."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = tmp_path / "bench"
    shutil.copy(b / "configs" / "mamba2-2.7b.json", b / "configs" / "new-cfg.json")
    shutil.copy(b / "configs" / "mamba2-2.7b.py", b / "configs" / "new-cfg.py")
    mix = json.loads((b / "traffic" / "chat-open.json").read_text())
    mix["rate_rps"] = 0.17
    (b / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (b / "metrics" / "new_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["configs"].append({"name": "new-cfg", "source": "x",
                            "file": "bench/configs/new-cfg.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new-cell", "config": "new-cfg",
                              "traffic": "new-mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_metric.x", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "x", "moves": "ttft_p90_s",
                              "workloads": ["new-cell"]})
    spec["end_to_end"][0]["workloads"].append("new-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    rs = harness.resolve(spec, "new-cell", root=str(tmp_path))
    assert rs["mix"]["rate_rps"] == 0.17
    assert rs["config"]["name"] == "mamba2-2.7b"
    assert hasattr(rs["reference"], "served_readings")
    assert {m["name"] for m in rs["end_to_end"]} == {"ttft_p90_s", "setup_s"}
    assert [m["name"] for m in rs["per_layer"]] == ["new_metric.x"]
    reader = harness.load_module(rs["readers"]["new_metric.x"], "m")
    assert reader.read(None) == 42.0
