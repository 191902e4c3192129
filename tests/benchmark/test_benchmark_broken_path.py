"""The rest of a run driven with the timed path broken underneath: the
harness's look for a chip is skipped (toy width, CPU) and ``correct``
must come out false, by the comparison that is there to catch the
fault."""

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def rehearse(cell_name, capsys, seconds):
    cell = harness.load_cell(cell_name)
    result = harness.run_cell(cell, seed=3_100_000_001, seconds=seconds,
                              trace=False, rehearse=True, t_start=0.0,
                              device=dict(CPU))
    failed = [ln.split()[2] for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("[bench] check ") and ln.endswith("FAILED")]
    return result, failed


def test_a_step_that_returns_its_state_unchanged(monkeypatch, capsys):
    import optax

    monkeypatch.setattr(optax, "apply_updates",
                        lambda params, updates: params)
    result, failed = rehearse("lm_train", capsys, 0.5)
    assert result["correct"] is False
    assert result["rehearsal_checks_ok"] is False
    # nothing moved: the parameters' change is all missing
    assert "update_norm_gap" in failed


def test_a_token_altered_where_it_is_produced(monkeypatch, capsys):
    from perceiver_tpu.serving.decode import DecodeEngine

    init = DecodeEngine.__init__

    def broken_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        exe, calls = self._exe, {"n": 0}
        vocab = self.graph.vocab_size

        def step(params, carry, tokens, qlens):
            carry, out = exe(params, carry, tokens, qlens)
            calls["n"] += 1
            if calls["n"] % 5 == 0:  # every fifth step serves another token
                out = dict(out)
                out["next_token"] = (
                    np.asarray(out["next_token"]) + 1) % vocab
            return carry, out

        self._exe = step

    monkeypatch.setattr(DecodeEngine, "__init__", broken_init)
    result, failed = rehearse("lm_decode", capsys, 4.0)
    assert result["correct"] is False
    assert result["rehearsal_checks_ok"] is False
    assert failed == ["token_logit_gap"]
    assert result["failed"] == 0  # every stream was served in full
