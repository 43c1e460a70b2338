"""The comparison that decides ``correct`` fails a broken timed path.

Each test drives a whole run at smoke size on the CPU, skipping only the
harness's look for a chip, with one fault planted in the program under
test, and sees ``correct`` come out false:

* a token altered where the sampler produces it;
* a decode step that returns its recurrent state unchanged;
* half of the decode batch left out: the odd rows get the even rows'
  logits.

There is one chip, so no exchange between chips can be left out.
"""
import pytest

from bench import smoke


def _altered_tokens(monkeypatch):
    from repro.serving import engine as E
    real, calls = E._sample_tokens, [0]

    def altered(key, logits, sampling):
        key, toks = real(key, logits, sampling)
        calls[0] += 1
        if calls[0] % 3 == 0:
            toks = (toks + 1) % logits.shape[-1]
        return key, toks
    monkeypatch.setattr(E, "_sample_tokens", altered)


def _state_unchanged(monkeypatch):
    import repro.ops as OPS
    real = OPS.state_update_step

    def unchanged(state, *args, **kw):
        _, y = real(state, *args, **kw)
        return state, y
    monkeypatch.setattr(OPS, "state_update_step", unchanged)


def _half_batch(monkeypatch):
    from repro.serving.memory.pool import PagedStatePool
    real = PagedStatePool.decode

    def half(self, *args, **kw):
        logits = real(self, *args, **kw)
        return logits.at[1::2].set(logits[0::2][:logits.shape[0] // 2])
    monkeypatch.setattr(PagedStatePool, "decode", half)


@pytest.mark.parametrize("workload,plant", [
    ("mamba2-chat-open", _altered_tokens),
    ("mamba2-chat-open", _state_unchanged),
    ("mamba2-chat-open", _half_batch),
], ids=["token-altered", "state-unchanged", "half-batch"])
def test_planted_fault_is_not_correct(monkeypatch, workload, plant):
    plant(monkeypatch)
    out = smoke.run(workload, seed=2 ** 31 + 11)
    assert out["check"]["compared_tokens"]["value"] > 0
    assert out["correct"] is False, out["check"]
