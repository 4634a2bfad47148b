"""Small cells for running the benchmark's harness on the CPU.

The configurations keep the shape of the benchmark's (a tied digital head
or an untied analog one, grouped q/k/v, a gated FFN) at widths a test can
hold.  ``tiny`` is the smallest, for the engine adapter and the faults,
which read far above any limit.  ``check_size`` is the least size at which
sound and control runs separated on every seed read on the CPU (d 256, 4
layers, vocabulary 8192; widest served-token gap over 9 seeds: tied head
0.078-0.180 sound, 0.277-0.449 control; analog head 0.234-0.516 sound,
0.859-1.297 control; mean gap: tied 0.008-0.016 vs 0.055-0.087, analog
0.027-0.053 vs 0.147-0.266), with limits between the two.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CHECK_LIMITS = {True: {"max_logit_gap": 0.23, "mean_logit_gap": 0.035},
                False: {"max_logit_gap": 0.7, "mean_logit_gap": 0.1}}


def tiny(tied: bool) -> tuple[dict, dict, dict]:
    """(cell, configuration, mix) at a test size."""
    name = "qwen1.5-0.5b" if tied else "qwen2.5-14b-1chip"
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4 if tied else 2, num_hidden_layers=2,
               vocab_size=500)
    cfg["calibration"] = {"rows": 2, "length": 32}
    cfg["correct"] = {"max_logit_gap": 0.06 if tied else 0.4}
    mix = json.loads((ROOT / "bench" / "traffic" / "chat-b48.json").read_text())
    mix["prompt"] = {"median": 20, "sigma": 0.5, "min": 4, "max": 40}
    mix["output"] = {"median": 8, "sigma": 0.5, "min": 3, "max": 16}
    mix["engine"] = {"slots": 4, "chunk": 16, "page_size": 8, "max_context": 56}
    mix["check"] = {"tokens": 24, "requests": 4}
    cell = {"name": "qwen05b-decode"}
    return cell, cfg, mix


def check_size(tied: bool) -> tuple[dict, dict, dict]:
    """(cell, configuration, mix) at the size the check's own tests use."""
    cell, cfg, mix = tiny(tied)
    cfg.update(hidden_size=256, intermediate_size=704, num_hidden_layers=4,
               num_key_value_heads=4 if tied else 1, vocab_size=8192)
    cfg["correct"] = dict(CHECK_LIMITS[tied])
    mix["check"] = {"tokens": 48, "requests": 4}
    return cell, cfg, mix


def run_cell(parts: tuple[dict, dict, dict], seed: int, seconds: float = 1.0,
             control: bool = False) -> dict:
    cell, cfg, mix = parts
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.run(cell, cfg, mix, manifest, seed, seconds, False,
                       time.time(), control=control)


def run_tiny(tied: bool, seed: int, seconds: float = 1.0,
             control: bool = False) -> dict:
    return run_cell(tiny(tied), seed, seconds, control)


@pytest.fixture
def no_compile_cache():
    """Keep the CPU runs of these tests out of any persistent cache."""
    import jax
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)
