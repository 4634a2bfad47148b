"""The benchmark's check, driven on the CPU at a small size: a sound run is
correct, and each fault a serving cell can have makes ``correct`` false.

Each test skips the harness's look for a chip (it calls ``harness.run``
directly) and breaks the timed path underneath: a decode step that returns
its KV state unchanged, or one token altered where the engine produces it.
"""
from __future__ import annotations

import pytest

from benchcells import check_size, no_compile_cache, run_cell, run_tiny  # noqa: F401


@pytest.mark.parametrize("tied", [True, False], ids=["tied-head", "analog-head"])
def test_sound_run_is_correct(tied, no_compile_cache):
    out = run_cell(check_size(tied), seed=2**35 + 3)
    assert out["correct"], out["checks"]
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert out["checks"][name]["value"] <= out["checks"][name]["limit"]
    assert list(out)[-1] == "checks"
    assert out["metrics"]["tok_s"]["value"] > 0


@pytest.mark.parametrize("tied", [True, False], ids=["tied-head", "analog-head"])
def test_decode_state_unchanged_is_caught(tied, monkeypatch, no_compile_cache):
    from repro.models import model
    real = model.decode_slots

    def stale(params, batch, caches, cfg, calib=None, windows=None):
        logits, _ = real(params, batch, caches, cfg, calib=calib, windows=windows)
        return logits, caches

    monkeypatch.setattr(model, "decode_slots", stale)
    out = run_tiny(tied, seed=23)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > out["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("tied", [True, False], ids=["tied-head", "analog-head"])
def test_altered_token_is_caught(tied, monkeypatch, no_compile_cache):
    from repro.runtime import engine as eng
    real = eng.Engine._emit

    def emit(self, slot, tok):
        if len(slot.record.tokens) == 2:        # the third token of each request
            tok = (tok + 1) % self.cfg.vocab_size
        return real(self, slot, tok)

    monkeypatch.setattr(eng.Engine, "_emit", emit)
    out = run_tiny(tied, seed=25)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > out["checks"]["max_logit_gap"]["limit"]
