#!/usr/bin/env python3
"""Chip smoke test: serve Qwen1.5-0.5B on a TPU through the engine, with
every analog site on the Pallas TD-VMM kernel.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # 2x2 (data, model) mesh vs meshless

One chip: the model at its published widths (24 layers, d_model 1024, 16
heads, d_ff 2816, vocab 151936, bf16; weights random from ``--seed``), a plan
that enables every TD-VMM site with backend ``auto``, ``model.calibrate`` on
a seeded batch, and a seeded ragged trace through ``Engine.run`` — the calls
``launch/serve.py:serve_engine`` makes.  It fails unless the run compiled
exactly two step programs, produced no NaN logits, finished every request,
planned its kernels from the Mosaic table, put the kernel
(``tpu_custom_call``) into the compiled decode step, and matched — window
for window and token for token — the same calibration and trace with every
site on backend ``jnp``.  Like the serving entry points, it runs with
XLA's bf16 rounding honored (``launch/xla_setup.honor_bf16_rounding``),
without which the two backends' programs round differently.

``--chips 4`` runs only the mesh phase: the same model and trace on a 2x2
(data, model) mesh against the meshless engine on one device of the same
host, in one process; per-request streams must be equal and each engine
must hold two compiled steps.

There is no CPU fallback: without a TPU the script exits non-zero and prints
no result.  The last line of standard output is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  Timings printed on
the way are smoke information, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileLog:
    """Backend compile durations, tagged with the phase that caused them."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_):
        if name.endswith("backend_compile_duration"):
            self.events.append((self.phase, secs))

    def seconds(self, phase: str, floor: float = 0.5) -> list[float]:
        return [round(s, 2) for p, s in self.events if p == phase and s >= floor]


def plan_cfg(cfg, backend: str):
    """``cfg`` with every TD-VMM site enabled on ``backend``."""
    from repro.configs import TDVMMPlan, tdvmm_rule
    return cfg.replace(tdvmm_plan=TDVMMPlan(
        rules=(tdvmm_rule("*", enabled=True, backend=backend),)))


def make_trace(vocab: int, seed: int, n: int, prompt: tuple[int, int],
               new: tuple[int, int]):
    """Seeded ragged trace: prompt and output lengths uniform in the given
    inclusive ranges, arrivals 0-2 engine steps apart."""
    import numpy as np

    from repro.runtime.engine import Request
    rng = np.random.default_rng(seed)
    reqs, arrival = [], 0
    for rid in range(n):
        length = int(rng.integers(prompt[0], prompt[1] + 1))
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(t) for t in rng.integers(0, vocab, length)),
            max_new_tokens=int(rng.integers(new[0], new[1] + 1)),
            arrival_step=arrival))
        arrival += int(rng.integers(0, 3))
    return reqs


def engine_config(slots: int, page_size: int, chunk: int, max_len: int):
    from repro.runtime.engine import EngineConfig
    from repro.runtime.paged_cache import pages_for
    per_slot = pages_for(max_len, page_size)
    return EngineConfig(slots=slots, page_size=page_size, chunk=chunk,
                        num_pages=slots * per_slot,
                        max_pages_per_slot=per_slot)


def calibration_batch(cfg, seed: int, rows: int, length: int) -> dict:
    import jax
    return {"inputs": jax.random.randint(
        jax.random.PRNGKey(seed + 1), (rows, length), 0, cfg.vocab_size)}


def streams(rep) -> list[tuple]:
    return [(r["rid"], tuple(r["tokens"]), r["finish_reason"])
            for r in rep.requests]


def check_report(name: str, rep, checks: dict) -> None:
    """The per-run checks every engine run of the smoke must pass."""
    checks[f"{name}.compiled_steps==2"] = rep.compiled_steps == 2
    checks[f"{name}.nan_steps==0"] = rep.nan_logit_steps == 0
    checks[f"{name}.all_finished"] = all(
        r["finish_reason"] in ("max_tokens", "eos") for r in rep.requests)
    log(f"{name}: {rep.generated_tokens} tokens in {rep.steps} steps "
        f"({rep.prefill_steps} chunk + {rep.decode_steps} decode), "
        f"compiled_steps={rep.compiled_steps}, "
        f"nan_steps={rep.nan_logit_steps}, wall {rep.wall_s:.2f} s "
        f"(compile included)")


def decode_hlo(engine) -> str:
    """Compiled text of the engine's decode step at its serving shapes."""
    import jax.numpy as jnp
    ecfg, st = engine.ecfg, engine._st
    b, cap = engine.total_slots, ecfg.resolved_max_pages
    batch = {"inputs": jnp.zeros((b, 1), jnp.int32),
             "block_tables": jnp.full((b, cap), st.pool.trash_page, jnp.int32),
             "pos": jnp.zeros((b,), jnp.int32),
             "active": jnp.zeros((b,), bool)}
    return engine._decode.lower(engine.params, batch, st.caches,
                                engine._windows).compile().as_text()


def single_chip(cfg, params, trace, ecfg, calib_batch, clog: CompileLog,
                checks: dict) -> None:
    """Pallas (backend auto) vs jnp: calibration windows, engine checks and
    token streams on one device."""
    import jax
    import numpy as np

    from repro.kernels.tdvmm import ops
    from repro.models import model
    from repro.runtime.engine import Engine

    max_len = calib_batch["inputs"].shape[1]
    runs = {}
    for backend in ("auto", "jnp"):
        bcfg = plan_cfg(cfg, backend)
        clog.phase = f"calibrate.{backend}"
        t0 = time.perf_counter()
        calib = model.calibrate(params, calib_batch, bcfg, max_len=max_len)
        log(f"calibrate[{backend}]: {len(calib.windows)} sites in "
            f"{time.perf_counter() - t0:.1f} s, compiles >0.5 s: "
            f"{clog.seconds(clog.phase)}")
        clog.phase = f"engine.{backend}"
        engine = Engine(bcfg, params, ecfg, calib=calib)
        rep = engine.run(trace)
        log(f"engine[{backend}] compile seconds (prefill, decode order): "
            f"{clog.seconds(clog.phase)}")
        check_report(f"engine[{backend}]", rep, checks)
        runs[backend] = (calib, engine, rep)

    calib_p, engine_p, rep_p = runs["auto"]
    calib_j, _, rep_j = runs["jnp"]
    same_windows = set(calib_p.windows) == set(calib_j.windows) and all(
        np.array_equal(np.asarray(calib_p.windows[s]),
                       np.asarray(calib_j.windows[s]))
        for s in calib_p.windows)
    for site in sorted(calib_p.windows):
        log(f"window {site}: pallas {np.asarray(calib_p.windows[site])} "
            f"jnp {np.asarray(calib_j.windows.get(site))}")
    checks["calibrate.pallas==jnp"] = same_windows
    sp, sj = streams(rep_p), streams(rep_j)
    differ = [a[0] for a, b in zip(sp, sj) if a != b]
    checks["streams.pallas==jnp"] = not differ and len(sp) == len(sj)
    log(f"pallas vs jnp token streams: {len(sp) - len(differ)}/{len(sp)} "
        f"requests identical{'' if not differ else f', differ: {differ}'}")

    report = ops.autotune_report()
    checks["autotune.platform==mosaic"] = report["platform"] == "mosaic"
    log(f"autotune: platform {report['platform']}, "
        f"{len(report['entries'])} shapes, {len(report['misses'])} misses "
        f"{report['misses']}")
    clog.phase = "hlo"
    checks["decode_hlo.tpu_custom_call"] = \
        "tpu_custom_call" in decode_hlo(engine_p)

    # Warm replay of the same trace on the compiled pallas engine: steady
    # state tokens/s (information only) and run-to-run determinism.
    clog.phase = "warm"
    warm = engine_p.run(trace)
    checks["warm.streams==cold"] = streams(warm) == sp
    checks["warm.compiled_steps==2"] = warm.compiled_steps == 2
    log(f"warm replay: {warm.generated_tokens / max(warm.wall_s, 1e-9):.1f} "
        f"tok/s over {warm.wall_s:.2f} s, new compiles: "
        f"{clog.seconds('warm', 0.0)}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


def four_chips(cfg, params, trace, ecfg, calib_batch, clog: CompileLog,
               checks: dict) -> None:
    """2x2 (data, model) mesh engine vs the meshless engine, one process."""
    from repro.launch.mesh import make_test_mesh
    from repro.models import model
    from repro.runtime.engine import Engine

    bcfg = plan_cfg(cfg, "auto")
    clog.phase = "calibrate"
    calib = model.calibrate(params, calib_batch, bcfg,
                            max_len=calib_batch["inputs"].shape[1])
    reps = {}
    for name, mesh in (("meshless", None), ("mesh2x2", make_test_mesh(2, 2))):
        clog.phase = name
        rep = Engine(bcfg, params, ecfg, calib=calib, mesh=mesh).run(trace)
        log(f"engine[{name}] devices={rep.devices} "
            f"total_slots={rep.total_slots}, compile seconds: "
            f"{clog.seconds(name)}")
        check_report(f"engine[{name}]", rep, checks)
        reps[name] = rep
    sa, sb = streams(reps["meshless"]), streams(reps["mesh2x2"])
    differ = [a[0] for a, b in zip(sa, sb) if a != b]
    checks["streams.mesh2x2==meshless"] = not differ and len(sa) == len(sb)
    log(f"mesh vs meshless token streams: {len(sa) - len(differ)}/{len(sa)} "
        f"requests identical{'' if not differ else f', differ: {differ}'}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.launch.xla_setup import honor_bf16_rounding, use_persistent_cache
    honor_bf16_rounding()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU visible (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, {len(devices)} visible", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.models import model

    log(f"device: {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {use_persistent_cache()}, "
        f"XLA_FLAGS {os.environ.get('XLA_FLAGS')!r}")
    clog = CompileLog()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = model.init_params(jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.param_count() / 1e6:.0f}M params, init "
        f"{time.perf_counter() - t0:.1f} s")
    prompt, new = (64, 512), (16, 64)
    trace = make_trace(cfg.vocab_size, args.seed, 16, prompt, new)
    ecfg = engine_config(slots=8, page_size=16, chunk=128,
                         max_len=prompt[1] + new[1])
    calib_batch = calibration_batch(cfg, args.seed, rows=8, length=128)

    checks: dict[str, bool] = {}
    phase = four_chips if args.chips == 4 else single_chip
    phase(cfg, params, trace, ecfg, calib_batch, clog, checks)

    failed = sorted(k for k, v in checks.items() if not v)
    for name in sorted(checks):
        log(f"check {name}: {'ok' if checks[name] else 'FAILED'}")
    ok = not failed
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
