"""Serving driver: by default a thin CLI over the continuous-batching
engine (``runtime/engine.py`` — paged KV cache, slot scheduler, chunked
prefill, per-request energy accounting):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
        --requests 8 --slots 4 --chunk 16 --calibrate

``--static`` keeps the legacy uniform-batch fast path (``serve()`` below:
one fixed-shape prefill + a fixed number of decode steps for a uniform
batch, optionally mesh-sharded with cache donation) — still the right tool
for uniform offline batches and the only path for SSM/hybrid archs.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke as smoke_cfg
from repro.launch import meshctx, sharding
from repro.launch.mesh import axis_info
from repro.launch.xla_setup import honor_bf16_rounding, use_persistent_cache
from repro.models import model


def serve(cfg, batch: int, prompt_len: int, gen: int, mesh=None, seed: int = 0,
          calibrate: bool = False, calib=None, plan_report: bool = False):
    """Prefill + decode driver.

    ``calibrate=True`` runs the model-wide §3.1 readout-window pass
    (models.model.calibrate) on the prompt batch before jitting, then serves
    with every TD-VMM site's window pinned — no per-call max|z|, fused
    Pallas epilogue eligible.  Pass a restored ``CalibrationState`` as
    ``calib`` to skip the capture pass (e.g. from
    checkpoint.restore_calibration).  ``plan_report`` prints the resolved
    site table (which boundaries are digital vs time-chained).
    """
    key = jax.random.PRNGKey(seed)
    if plan_report:
        print("[serve] TD-VMM plan:")
        print(cfg.resolved_tdvmm_plan.describe())

    if cfg.input_mode == "tokens":
        prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
        step_in = {"inputs": prompts}
    else:
        step_in = {"inputs": jax.random.normal(
            key, (batch, prompt_len, cfg.d_model), jnp.float32)}

    if mesh is not None:
        info = axis_info(mesh)
        meshctx.set_mesh(mesh, info["dp_axes"], info["tp_axis"])
        params_shape = jax.eval_shape(lambda: model.init_params(key, cfg))
        p_specs = sharding.param_specs(params_shape, cfg, mesh)
        p_sh = sharding.to_named(p_specs, mesh)
        with mesh:
            params = jax.jit(lambda k: model.init_params(k, cfg),
                             out_shardings=p_sh)(key)
            caches_shape = jax.eval_shape(
                lambda: model.init_caches(cfg, batch, prompt_len + gen))
            c_specs = sharding.cache_specs(caches_shape, cfg, mesh)
            c_sh = sharding.to_named(c_specs, mesh)
            caches = jax.jit(lambda: model.init_caches(cfg, batch, prompt_len + gen),
                             out_shardings=c_sh)()
            if calibrate and calib is None:
                calib = model.calibrate(params, step_in, cfg,
                                        max_len=prompt_len + gen)
            prefill = jax.jit(
                lambda p, b, c: model.prefill_step(p, b, c, cfg, calib=calib),
                donate_argnums=(2,), out_shardings=(None, c_sh))
            decode = jax.jit(
                lambda p, b, c: model.decode_step(p, b, c, cfg, calib=calib),
                donate_argnums=(2,), out_shardings=(None, c_sh))
    else:
        params = model.init_params(key, cfg)
        caches = model.init_caches(cfg, batch, prompt_len + gen)
        if calibrate and calib is None:
            # One eager prefill with the collector installed; the captured
            # per-site windows are then closed over as jit-static settings.
            calib = model.calibrate(params, step_in, cfg,
                                    max_len=prompt_len + gen)
        prefill = jax.jit(
            lambda p, b, c: model.prefill_step(p, b, c, cfg, calib=calib),
            donate_argnums=(2,))
        decode = jax.jit(
            lambda p, b, c: model.decode_step(p, b, c, cfg, calib=calib),
            donate_argnums=(2,))

    t0 = time.time()
    logits, caches = prefill(params, step_in, caches)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for _ in range(gen - 1):
        if cfg.input_mode == "tokens":
            nxt = {"inputs": tok}
        else:
            nxt = {"inputs": jax.random.normal(key, (batch, 1, cfg.d_model))}
        logits, caches = decode(params, nxt, caches)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    tokens = jnp.concatenate(out_tokens, axis=1)
    return {
        "tokens": tokens,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
        "calibration": calib,
    }


def _parse_alert_spec(spec: str):
    """One ``--alert-on`` value -> AlertRule.

    Format: ``metric:kind[:key=val[,key=val...]]``, e.g.
    ``step_latency_s:spike:k=6,abs_floor=0.05`` or
    ``fj_per_op:regression:baseline=57.1,tol=0.1``."""
    from repro.runtime.telemetry import AlertRule
    parts = spec.split(":", 2)
    if len(parts) < 2:
        raise SystemExit(f"--alert-on {spec!r}: want metric:kind[:k=v,...]")
    metric, kind = parts[0], parts[1]
    kwargs = {}
    if len(parts) == 3 and parts[2]:
        for kv in parts[2].split(","):
            k, _, v = kv.partition("=")
            if not _:
                raise SystemExit(f"--alert-on {spec!r}: bad param {kv!r}")
            kwargs[k] = int(v) if k in ("min_samples",) else float(v)
    try:
        return AlertRule(metric=metric, kind=kind, **kwargs)
    except (TypeError, ValueError) as e:
        raise SystemExit(f"--alert-on {spec!r}: {e}")


def _make_sink(args):
    """The telemetry MetricsSink for this run (None = telemetry off).

    Enabled by ``--metrics-jsonl`` and/or ``--alert-on``.  With no explicit
    rules a default step-latency spike detector is installed (median +
    6*MAD with a 50 ms absolute deadband — jit-compile steps on a cold
    engine will legitimately alert; warm traffic won't)."""
    from repro.runtime import telemetry as tele
    if not (args.metrics_jsonl or args.alert_on):
        return None
    rules = [_parse_alert_spec(s) for s in (args.alert_on or [])]
    if not rules:
        rules = [tele.AlertRule("step_latency_s", kind="spike", k=6.0,
                                abs_floor=0.05)]
    emitters = [tele.StdoutEmitter()]
    if args.metrics_jsonl:
        emitters.append(tele.JsonlEmitter(args.metrics_jsonl))
    return tele.MetricsSink(rules=rules, emitters=emitters)


def _fault_config(args, probe_batch=None, sink=None):
    """Assemble the engine FaultConfig from CLI flags (None = no wiring).

    A real PreemptionGuard with SIGTERM/SIGINT handlers is installed when a
    snapshot dir is given, so an actual eviction snapshots the in-flight
    state; ``--preempt-at``/``--fail-at``/``--drift-at``/``--slow-at``
    inject the same faults deterministically at a chosen engine step.  A
    telemetry ``sink`` threads into the straggler monitor and heartbeat so
    their events land in the metric series too."""
    from repro.runtime import fault
    from repro.runtime import faultinject as fi
    from repro.runtime.engine import DriftConfig, FaultConfig

    events = []
    if args.preempt_at is not None:
        events.append(fi.PreemptAt(args.preempt_at))
    if args.fail_at is not None:
        events.append(fi.FailStep(step=args.fail_at, kind=args.fail_kind,
                                  times=args.fail_times))
    if args.drift_at is not None:
        events.append(fi.DriftAt(args.drift_at, sigma=args.drift_sigma))
    if args.slow_at is not None:
        events.append(fi.SlowStep(args.slow_at, sleep_s=args.slow_sleep))
    drift = None
    if args.drift_check_every > 0 or args.clip_observe_every > 0:
        if probe_batch is None:
            raise SystemExit("--drift-check-every/--clip-observe-every "
                             "require --calibrate (the probe compares "
                             "against the pinned calibration windows)")
        drift = DriftConfig(probe_batch=probe_batch,
                            # observe-only wiring leaves the full check
                            # effectively off (clip alerts still stream)
                            check_every=args.drift_check_every or 10**9,
                            clip_threshold=args.drift_clip,
                            window_tol=args.drift_tol,
                            observe_every=args.clip_observe_every)
    hb = (fault.Heartbeat(args.heartbeat, args.heartbeat_every, sink=sink)
          if args.heartbeat else None)
    if not (events or drift or hb or args.snapshot_dir):
        return None
    guard = None
    if args.snapshot_dir:
        guard = fault.PreemptionGuard().install()
    return FaultConfig(
        guard=guard, snapshot_dir=args.snapshot_dir, retries=args.retries,
        injector=fi.FaultInjector(events) if events else None,
        drift=drift, heartbeat=hb,
        monitor=fault.StragglerMonitor(sink=sink))


def serve_engine(cfg, args, seed: int = 0):
    """Engine path: synthetic ragged trace -> continuous-batching run,
    optionally fault-wired (snapshot/resume, injection, drift probing)."""
    import numpy as np

    from repro.runtime.engine import Engine, EngineConfig, Request

    from repro.launch.mesh import parse_mesh

    mesh = parse_mesh(args.mesh)
    if mesh is not None:
        print(f"[serve] mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}"
              f" over {mesh.size} devices")
    key = jax.random.PRNGKey(seed)
    params = model.init_params(key, cfg)
    calib = None
    calib_batch = None
    if args.calibrate:
        calib_batch = {"inputs": jax.random.randint(
            key, (min(args.slots, 4), args.prompt_len), 0, cfg.vocab_size)}
        calib = model.calibrate(params, calib_batch, cfg,
                                max_len=args.prompt_len + args.gen)
    if args.plan_report:
        print("[serve] TD-VMM plan:")
        print(cfg.resolved_tdvmm_plan.describe())

    sla = None
    if args.sla:
        from repro.runtime.sla import SlaConfig
        sla = SlaConfig(aging_steps=args.aging_steps)
    sink = _make_sink(args)
    tracer = None
    if args.trace_out:
        from repro.runtime.trace import Tracer
        tracer = Tracer()

    rng = np.random.default_rng(seed)
    lo, hi = max(1, args.prompt_len // 4), args.prompt_len + 1
    reqs = []
    arrival = 0
    for rid in range(args.requests):
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(t) for t in
                         rng.integers(0, cfg.vocab_size, rng.integers(lo, hi))),
            max_new_tokens=int(rng.integers(max(1, args.gen // 4), args.gen + 1)),
            arrival_step=arrival,
            # SLA fields are inert without --sla (defaults replay FIFO)
            priority=(rid % 3) if args.sla else 0,
            deadline_steps=args.deadline_steps,
            joule_budget=args.joule_budget))
        arrival += int(rng.integers(0, 3))
    # Block-table width (= per-slot attention span) sized to the workload,
    # not the pool: every decode step gathers max_pages_per_slot pages per
    # slot, so leaving it at num_pages would attend over mostly-trash keys.
    from repro.runtime.paged_cache import pages_for
    max_pages = min(args.num_pages,
                    pages_for(args.prompt_len + args.gen, args.page_size))
    ecfg = EngineConfig(slots=args.slots, page_size=args.page_size,
                        num_pages=args.num_pages, chunk=args.chunk,
                        max_pages_per_slot=max_pages)
    fc = _fault_config(args, probe_batch=calib_batch, sink=sink)
    if args.resume:
        # Resume a preempted run: the snapshot carries the full in-flight
        # state INCLUDING the pinned (possibly recalibrated) windows — build
        # the engine's calibration from them, then restore and continue.
        from repro.checkpoint import checkpoint
        from repro.core.calibration import CalibrationState

        if not args.snapshot_dir:
            raise SystemExit("--resume requires --snapshot-dir")
        flat, step = checkpoint.load_engine_snapshot(args.snapshot_dir)
        calib = CalibrationState(windows={
            k.split("/", 1)[1]: jnp.asarray(v) for k, v in flat.items()
            if k.startswith("windows/")})
        engine = Engine(cfg, params, ecfg, calib=calib, sla=sla, sink=sink,
                        mesh=mesh, tracer=tracer)
        engine.restore(flat)
        print(f"[serve] resumed from snapshot step {step} "
              f"({args.snapshot_dir})")
        rep = engine.resume(fc)
    else:
        engine = Engine(cfg, params, ecfg, calib=calib, sla=sla, sink=sink,
                        mesh=mesh, tracer=tracer)
        rep = engine.run(reqs, fc)
    if rep.preempted:
        print(f"[serve] PREEMPTED at step {rep.steps}; snapshot: "
              f"{rep.snapshot_path} (resume with --resume)")
    if rep.step_retries or rep.failed:
        print(f"[serve] faults: {rep.step_retries} step retries, "
              f"{rep.failed} requests failed")
    if rep.recalibrations or rep.drift_events:
        print(f"[serve] drift: {len(rep.drift_events)} events, "
              f"{rep.recalibrations} online recalibrations "
              f"(compiled steps still {rep.compiled_steps})")
    print(f"[serve] engine: {len(reqs)} requests, "
          f"{rep.generated_tokens} tokens in {rep.steps} steps "
          f"({rep.prefill_steps} chunk + {rep.decode_steps} decode, "
          f"{rep.generated_tokens / max(rep.wall_s, 1e-9):.1f} tok/s), "
          f"utilization {rep.utilization:.2f}, "
          f"KV high-water {rep.kv_high_water_bytes / 1024:.1f} KiB, "
          f"compiled steps = {rep.compiled_steps}")
    if rep.analog_ops:
        print(f"[serve] analog: {rep.analog_ops:.3g} Ops, "
              f"{rep.fj_per_op:.2f} fJ/Op, "
              f"{rep.tokens_per_joule:.3g} tok/J")
    if sla is not None:
        print(f"[serve] sla: {rep.rejected} rejected at admission, "
              f"{rep.over_budget} over budget, deadlines "
              f"{rep.deadline_hits} hit / {rep.deadline_misses} missed")
    if sink is not None:
        tel = rep.telemetry or {}
        print(f"[serve] telemetry: {tel.get('observations', 0)} samples, "
              f"{rep.alerts} alerts "
              f"({', '.join(f'{k}={v}' for k, v in sorted(tel.get('alerts_by_rule', {}).items())) or 'none'})")
        if args.metrics_jsonl:
            print(f"[serve] metrics streamed to {args.metrics_jsonl}")
        for em in sink.emitters:
            em.close()
    if tracer is not None:
        import json
        from pathlib import Path
        doc = tracer.chrome_trace()
        Path(args.trace_out).write_text(json.dumps(doc))
        summ = rep.trace_summary or {}
        pct = (summ.get("percentiles") or {}).get("total_us", {})
        print(f"[serve] trace: {len(doc['traceEvents'])} events over "
              f"{summ.get('ticks', 0)} ticks -> {args.trace_out} "
              f"(request total p50 {pct.get('p50', 0.0):.0f} us / "
              f"p95 {pct.get('p95', 0.0):.0f} us; open in Perfetto)")
    for r in rep.requests[:4]:
        print(f"[serve]   req {r['rid']}: {r['finish_reason']} "
              f"tokens={r['tokens'][:8]}")
    if args.report_json:
        import json
        from pathlib import Path
        Path(args.report_json).write_text(json.dumps(rep.to_json(), indent=1))
        print(f"[serve] report written to {args.report_json}")
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--static", action="store_true",
                    help="legacy uniform-batch path (serve(); required for "
                         "SSM/hybrid archs)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--calibrate", action="store_true",
                    help="model-wide TD-VMM readout-window calibration pass "
                         "before serving (pins every site's ADC window)")
    ap.add_argument("--plan-report", action="store_true",
                    help="print the resolved TD-VMM site table")
    # engine knobs
    ap.add_argument("--requests", type=int, default=8,
                    help="engine path: synthetic ragged trace size")
    ap.add_argument("--mesh", default=None, metavar="DxT",
                    help="engine path: serve over a (data, model) mesh, e.g. "
                         "2x2 (needs D*T visible devices — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count). "
                         "DP multiplies the slot pool: total slots = D * "
                         "--slots")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=64)
    # fault tolerance & drift (engine path)
    ap.add_argument("--snapshot-dir", default=None,
                    help="preemption snapshots go here; also installs real "
                         "SIGTERM/SIGINT handlers")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest engine snapshot from "
                         "--snapshot-dir and continue the trace")
    ap.add_argument("--preempt-at", type=int, default=None,
                    help="inject a preemption at this engine step")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a compiled-step failure at this step")
    ap.add_argument("--fail-kind", default="any",
                    choices=["prefill", "decode", "any"])
    ap.add_argument("--fail-times", type=int, default=1,
                    help="how many raises (<= --retries: transient; "
                         "--retries+1: persistent, one request fails)")
    ap.add_argument("--drift-at", type=int, default=None,
                    help="perturb device currents (FG tuning drift) at "
                         "this step")
    ap.add_argument("--drift-sigma", type=float, default=0.5)
    ap.add_argument("--slow-at", type=int, default=None,
                    help="inject a one-step straggler (inflated wall time) "
                         "at this engine step")
    ap.add_argument("--slow-sleep", type=float, default=0.25,
                    help="seconds the injected straggler step sleeps")
    # SLA scheduling & telemetry (engine path)
    ap.add_argument("--sla", action="store_true",
                    help="SLA admission/dispatch: priority-with-aging "
                         "(trace priorities cycle rid %% 3), deadline/joule "
                         "admission control, over-budget enforcement")
    ap.add_argument("--aging-steps", type=int, default=16,
                    help="queue-wait steps per priority level of aging")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="per-request deadline (engine steps after arrival) "
                         "stamped on every trace request")
    ap.add_argument("--joule-budget", type=float, default=None,
                    help="per-request analog energy budget in joules "
                         "stamped on every trace request")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="stream per-tick metrics + alerts to this JSONL "
                         "file (enables the telemetry sink)")
    ap.add_argument("--alert-on", action="append", default=None,
                    metavar="METRIC:KIND[:K=V,...]",
                    help="telemetry alert rule, e.g. "
                         "step_latency_s:spike:k=6,abs_floor=0.05 or "
                         "fj_per_op:regression:baseline=57.1,tol=0.1 "
                         "(repeatable; enables the telemetry sink)")
    ap.add_argument("--retries", type=int, default=2,
                    help="retry budget per compiled step")
    ap.add_argument("--heartbeat", default=None,
                    help="liveness marker file path")
    ap.add_argument("--heartbeat-every", type=float, default=30.0)
    ap.add_argument("--drift-check-every", type=int, default=0,
                    help="probe for window drift every N engine steps "
                         "(0 = off; requires --calibrate)")
    ap.add_argument("--drift-tol", type=float, default=0.25,
                    help="max |log window ratio| before recalibrating")
    ap.add_argument("--drift-clip", type=float, default=0.01,
                    help="max readout clip rate before recalibrating")
    ap.add_argument("--clip-observe-every", type=int, default=0,
                    help="stream per-site readout clip rates into the "
                         "telemetry sink every N engine steps as "
                         "clip_rate.<site> series (0 = off; requires "
                         "--calibrate and analog sites, e.g. "
                         "--tdvmm 'ffn.*'; pair with --alert-on "
                         "'clip_rate.ffn.out:threshold:limit=0.01')")
    ap.add_argument("--tdvmm", default=None, metavar="PATTERN",
                    help="enable analog TD-VMM at the plan sites matching "
                         "PATTERN (e.g. 'ffn.*'); stock arch configs ship "
                         "all-digital, so clip_rate series and per-site "
                         "attribution need this (backend auto: the Pallas "
                         "kernel on a TPU, jnp elsewhere)")
    ap.add_argument("--trace-out", default=None,
                    help="engine path: write a Chrome-trace/Perfetto JSON "
                         "of the whole request lifecycle here (spans ride "
                         "engine snapshots, so a --resume run continues "
                         "the same trace)")
    ap.add_argument("--report-json", default=None,
                    help="engine path: write the full EngineReport here")
    args = ap.parse_args()
    honor_bf16_rounding()
    use_persistent_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_cfg(cfg)
    if args.tdvmm:
        from repro.configs import TDVMMPlan, tdvmm_rule
        cfg = cfg.replace(tdvmm_plan=TDVMMPlan(rules=(
            tdvmm_rule(args.tdvmm, enabled=True),)))
    if args.kv_int8:
        from repro.models import attention
        attention.set_kv_cache_int8(True)
    if not args.static:
        serve_engine(cfg, args)
        return
    out = serve(cfg, args.batch, args.prompt_len, args.gen,
                calibrate=args.calibrate, plan_report=args.plan_report)
    print(f"[serve] {args.arch} batch={args.batch} prefill={out['prefill_s']:.2f}s "
          f"decode={out['decode_s']:.2f}s ({out['decode_tok_per_s']:.1f} tok/s)")
    if out["calibration"] is not None:
        print(f"[serve] calibrated sites: {out['calibration'].sites()}")
    print("[serve] sample:", out["tokens"][0, :12].tolist())


if __name__ == "__main__":
    main()
