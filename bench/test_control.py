"""Each control, standing in for the program, comes out not correct by the
harness's own decision: at smoke size on the CPU, on the prompts and served
tokens of a run that the program passes, the plain reference in bfloat16
(the configuration computes in float32) and with a 4-bit recurrent state
(the configuration keeps MX8) each read a number over its limit.

``CHECK`` in ``bench/smoke.py`` is the smoke size's limit, from CPU
readings on four seeds: the program's ``prefill_err`` at most 6.6e-7, the
bf16 control's at least 0.0155 and the int4 control's at least 0.0875
(limit 1e-4); ``max_gap`` at most 0.0025 for the program (limit 0.04).  On
the chip the same readings, at the cell's own size and load, set the
cell's limits (``bench/readings.py``; PERF.md).  On the CPU the ``high``
control is the reference itself (CPU dots are float32 either way), so it
is read on the chip only."""
import pytest

from bench import smoke


@pytest.mark.parametrize("workload", ["mamba2-chat-open"])
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_control_is_not_correct(workload, seed):
    out = smoke.run(workload, seed=seed, controls=("bf16", "int4"))
    assert out["check"]["compared_tokens"]["value"] > 0
    assert out["correct"] is True, out["check"]
    for c in ("bf16", "int4"):
        assert out["controls"][c]["correct"] is False, out["controls"][c]
    assert list(out)[-1] == "check"
