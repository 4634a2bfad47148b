"""Serving driver: prefill+decode loop produces tokens, donates caches,
works with int8 KV; the engine CLI dumps a complete report."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, smoke
from repro.launch.serve import serve
from repro.models import attention


def test_serve_dense():
    cfg = smoke(get_config("qwen1.5-0.5b"))
    out = serve(cfg, batch=2, prompt_len=8, gen=4)
    assert out["tokens"].shape == (2, 4)
    assert out["decode_tok_per_s"] > 0


def test_serve_ssm_int8_kv():
    attention.set_kv_cache_int8(True)
    try:
        cfg = smoke(get_config("zamba2-2.7b"))
        out = serve(cfg, batch=2, prompt_len=8, gen=4)
        assert out["tokens"].shape == (2, 4)
    finally:
        attention.set_kv_cache_int8(False)


def test_engine_cli_report_json_is_complete(tmp_path, monkeypatch):
    """``--report-json`` dumps the FULL EngineReport — every dataclass
    field (including the SLA/telemetry ones) and per-request SLA outcomes —
    and ``--metrics-jsonl`` streams the per-tick series alongside."""
    from repro.launch import serve as serve_mod
    from repro.runtime.engine import EngineReport

    report = tmp_path / "report.json"
    jsonl = tmp_path / "metrics.jsonl"
    # Process set-up would outlive this test in the worker; it has its own
    # test (test_entry_point_setup).
    monkeypatch.setattr(serve_mod, "honor_bf16_rounding", lambda: None)
    monkeypatch.setattr(serve_mod, "use_persistent_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "qwen1.5-0.5b", "--smoke",
        "--requests", "3", "--slots", "2", "--prompt-len", "8",
        "--gen", "4", "--chunk", "8", "--page-size", "4",
        "--num-pages", "16", "--sla", "--deadline-steps", "500",
        "--metrics-jsonl", str(jsonl), "--report-json", str(report)])
    serve_mod.main()
    doc = json.loads(report.read_text())
    fields = {f.name for f in dataclasses.fields(EngineReport)}
    assert set(doc) == fields                    # nothing dropped, ever
    assert doc["compiled_steps"] == 2
    assert doc["telemetry"]["observations"] > 0
    assert doc["alerts"] == doc["telemetry"]["alerts"]
    # every trace request declared the 500-step deadline and made it
    assert doc["deadline_hits"] == 3 and doc["deadline_misses"] == 0
    for rec in doc["requests"]:
        for key in ("priority", "deadline_steps", "deadline_hit",
                    "joule_budget", "joules_used", "reject_reason"):
            assert key in rec, key
        assert rec["deadline_hit"] is True
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert sum(1 for ln in lines
               if ln["t"] == "metric" and ln["metric"] == "step_latency_s"
               ) == doc["telemetry"]["metrics"]["step_latency_s"]["count"]


SRC = str(Path(__file__).resolve().parents[1] / "src")
_SETUP_PROBE = """
import os, sys
from pathlib import Path
from repro.launch import xla_setup
xla_setup.CHECKOUT = Path(sys.argv[1])
xla_setup.honor_bf16_rounding()
print(xla_setup.use_persistent_cache())
print(os.environ["XLA_FLAGS"])
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


@pytest.mark.parametrize("cache_env", [False, True])
def test_entry_point_setup(tmp_path, cache_env):
    """Entry-point set-up: compiled entries land in JAX_COMPILATION_CACHE_DIR
    when it is set and nowhere else, else in <checkout>/.jax_cache; XLA is
    told to keep every declared bf16 rounding."""
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    cache = checkout / ".jax_cache"
    if cache_env:
        cache = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    out = subprocess.run([sys.executable, "-c", _SETUP_PROBE, str(checkout)],
                         env=env, capture_output=True, text=True, check=True,
                         timeout=300).stdout.splitlines()
    assert out == [str(cache), "--xla_allow_excess_precision=false"]
    assert any(cache.iterdir())
    written = {p.parent for p in tmp_path.rglob("*") if p.is_file()}
    assert written == {cache}
