"""Serving benchmark: a ragged synthetic trace through the
continuous-batching TD-VMM engine (``runtime/engine.py``).

Replays a fixed-seed trace (mixed prompt lengths, Poisson-ish arrival gaps,
per-request decode budgets) through the paged engine for two plan configs —
``ffn`` TD-VMM **unchained** vs **time-domain chained** (``ffn.in`` ->
``ffn.out``, Fig. 2: the intermediate p-bit readout disappears) — and emits
``BENCH_serving.json``: throughput, p50/p99 latency proxies
(steps-in-system), slot utilization, paged-KV memory high-water, and the
paper's currency measured at request level: fJ/Op, J/token,
tokens-per-joule.

Invariants (asserted by ``check_invariants`` in CI and ``benchmarks/run.py``):

  * the engine drains the ragged trace in fewer wall-steps than the legacy
    static uniform-batch ``serve()`` schedule, at higher decode utilization;
  * paged KV memory high-water < the dense ``batch * max_len`` allocation;
  * zero NaN logit rows (evict-before-poison), exactly TWO compiled steps;
  * per-request streams bit-identical to running the request alone at the
    same calibrated windows;
  * the chained plan spends fewer joules per token than the unchained one;
  * an engine killed mid-trace and restored from its snapshot resumes the
    remaining trace bit-identically to the uninterrupted baseline;
  * injected device-current drift triggers >= 1 online recalibration with
    ``compiled_steps`` still exactly 2 (hot-swapped runtime windows);
  * SLA scheduling (``serving_sla``): every admitted feasible deadline is
    hit, an infeasible request is rejected at admission with zero compute,
    an over-budget request degrades gracefully with neighbors bit-equal to
    their solo runs;
  * telemetry (``serving_telemetry_spike``): an injected straggler step
    raises exactly one rolling-median spike alert at the injected step,
    with zero false positives on the clean warm trace (metrics stream to
    ``BENCH_serving_metrics.jsonl``);
  * tracing (``serving_trace``): a traced replay streams bit-identically
    to the untraced reference, its Chrome trace validates (balanced B/E
    spans, monotonic timestamps per thread) with span boundaries matching
    the report's finish steps, and the per-site attribution table sums
    **bit-exactly** to the aggregate analog-ops / energy / fJ/Op counters
    (the chained plan's saved inter-site I/O is explicit per site).

Wall timings route through ``benchmarks.common`` (warmup + median of
repeats, spread recorded per row) so serving numbers carry the same
trust annotations as the kernel suite's.
"""
from __future__ import annotations

from pathlib import Path

import jax
import numpy as np

from benchmarks.common import Timing, emit, reset_rows, save_json, time_host
from repro.configs import TDVMMPlan, get_config, smoke, tdvmm_rule
from repro.models import model
from repro.runtime.engine import Engine, EngineConfig, Request, static_baseline

METRICS_JSONL = "BENCH_serving_metrics.jsonl"

ARCH = "qwen1.5-0.5b"

PLANS = {
    "ffn_unchained": TDVMMPlan(rules=(
        tdvmm_rule("ffn.*", enabled=True, backend="auto"),)),
    "ffn_chained": TDVMMPlan(rules=(
        tdvmm_rule("ffn.*", enabled=True, backend="auto"),
        tdvmm_rule("ffn.in", chain=True))),
}


def make_trace(vocab: int, n_requests: int = 10, seed: int = 0,
               prompt_lo: int = 4, prompt_hi: int = 14,
               gen_lo: int = 2, gen_hi: int = 25,
               max_gap: int = 1) -> list[Request]:
    """Fixed-seed ragged trace: uniform prompt/budget mix, arrival gaps
    drawn from [0, max_gap] (the Poisson-ish schedule — deterministic, so
    the scheduler-determinism and bit-identity invariants are replayable)."""
    rng = np.random.default_rng(seed)
    reqs, arrival = [], 0
    for rid in range(n_requests):
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(t) for t in rng.integers(
                0, vocab, rng.integers(prompt_lo, prompt_hi))),
            max_new_tokens=int(rng.integers(gen_lo, gen_hi)),
            arrival_step=arrival))
        arrival += int(rng.integers(0, max_gap + 1))
    return reqs


def _dense_cache_bytes(cfg, batch: int, max_len: int) -> int:
    shapes = jax.eval_shape(lambda: model.init_caches(cfg, batch, max_len))
    return int(sum(np.prod(leaf.shape) * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(shapes)))


def _percentile(xs: list[int], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def run(n_requests: int = 10):
    reset_rows()
    base = smoke(get_config(ARCH))
    key = jax.random.PRNGKey(0)
    params = model.init_params(key, base)
    trace = make_trace(base.vocab_size, n_requests=n_requests)
    max_len = max(len(r.prompt) + r.max_new_tokens for r in trace)
    # tile_n=64 matches the smoke model's d_model (a 256-tile would be >75%
    # padding waste on 64-wide matrices and swamp the fJ/Op signal); the
    # block-table width is sized to the longest request, not the pool, so
    # per-step attention doesn't span mostly-trash pages.
    from repro.runtime.paged_cache import pages_for
    ecfg = EngineConfig(slots=4, page_size=4, num_pages=64, chunk=8, tile_n=64,
                        max_pages_per_slot=pages_for(max_len, 4))

    static = static_baseline(trace, ecfg.slots, ecfg.chunk)
    dense_bytes = _dense_cache_bytes(base, ecfg.slots, max_len)

    reports, plan_ctx = {}, {}
    for name, plan in PLANS.items():
        cfg = base.replace(tdvmm_plan=plan)
        calib_batch = {"inputs": jax.random.randint(
            jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)}
        calib = model.calibrate(params, calib_batch, cfg, max_len=32)
        plan_ctx[name] = (cfg, calib, calib_batch)
        # One engine reused across warmup + repeats: run() re-initializes
        # all serving state, the instance keeps its jit caches, so the
        # median is post-compile wall time (PR 6 timing hygiene).
        engine = Engine(cfg, params, ecfg, calib=calib)
        rep, wall = time_host(lambda: engine.run(trace))
        reports[name] = rep

        # bit-identity: the first two requests replayed alone (B=1, same
        # chunking + calibrated windows) must stream identical tokens.
        solo_ok = True
        solo_ecfg = EngineConfig(slots=1, page_size=ecfg.page_size,
                                 num_pages=ecfg.num_pages, chunk=ecfg.chunk,
                                 max_pages_per_slot=ecfg.max_pages_per_slot)
        for req in trace[:2]:
            solo = Engine(cfg, params, solo_ecfg, calib=calib).run(
                [Request(req.rid, req.prompt, req.max_new_tokens, 0)])
            got = next(r for r in rep.requests if r["rid"] == req.rid)
            solo_ok &= solo.requests[0]["tokens"] == got["tokens"]

        sis = [r["steps_in_system"] for r in rep.requests
               if r["finished_step"] >= 0]
        tokens_proc = rep.prompt_tokens + rep.generated_tokens
        # us_per_call = median post-warmup wall time PER ENGINE STEP, with
        # the repeat count and (per-step) spread riding on the Timing.
        steps = max(rep.steps, 1)
        emit(f"serving_engine_{name}",
             Timing(float(wall) / steps, wall.repeats,
                    wall.spread_us / steps),
             f"steps={rep.steps}|util={rep.utilization:.2f}"
             f"|fJ_per_op={rep.fj_per_op:.2f}",
             data={
                 "requests": len(trace),
                 "wall_steps": rep.steps,
                 "prefill_steps": rep.prefill_steps,
                 "decode_steps": rep.decode_steps,
                 "idle_steps": rep.idle_steps,
                 "generated_tokens": rep.generated_tokens,
                 "prompt_tokens": rep.prompt_tokens,
                 "tok_per_s_wall":
                     rep.generated_tokens / max(float(wall) / 1e6, 1e-9),
                 "utilization": rep.utilization,
                 "evictions": rep.evictions,
                 "nan_logit_steps": rep.nan_logit_steps,
                 "p50_steps_in_system": _percentile(sis, 50),
                 "p99_steps_in_system": _percentile(sis, 99),
                 "page_high_water": rep.page_high_water,
                 "kv_high_water_bytes": rep.kv_high_water_bytes,
                 "analog_ops": rep.analog_ops,
                 "analog_energy_j": rep.analog_energy_j,
                 "fj_per_op": rep.fj_per_op,
                 "j_per_token": (rep.analog_energy_j / tokens_proc
                                 if tokens_proc else 0.0),
                 "tokens_per_joule": rep.tokens_per_joule,
                 "compiled_steps": rep.compiled_steps,
                 "bit_identical_solo": solo_ok,
             })

    ref = reports["ffn_unchained"]
    emit("serving_vs_static", 0.0,
         f"engine={ref.steps}steps vs static={static['wall_steps']}",
         data={
             "engine_wall_steps": ref.steps,
             "static_wall_steps": static["wall_steps"],
             "engine_beats_static_steps": ref.steps < static["wall_steps"],
             "engine_utilization": ref.utilization,
             "static_utilization": static["utilization"],
             "engine_beats_static_utilization":
                 ref.utilization > static["utilization"],
             "kv_high_water_bytes": ref.kv_high_water_bytes,
             "dense_cache_bytes": dense_bytes,
             "paged_beats_dense_memory":
                 ref.kv_high_water_bytes < dense_bytes,
         })

    un, ch = reports["ffn_unchained"], reports["ffn_chained"]
    emit("serving_energy_chained_vs_unchained", 0.0,
         f"J/tok {ch.analog_energy_j:.3g} vs {un.analog_energy_j:.3g}",
         data={
             "unchained_energy_j": un.analog_energy_j,
             "chained_energy_j": ch.analog_energy_j,
             "unchained_tokens_per_joule": un.tokens_per_joule,
             "chained_tokens_per_joule": ch.tokens_per_joule,
             "chained_saves_energy":
                 ch.analog_energy_j < un.analog_energy_j,
         })

    # --- fault tolerance: kill mid-trace, snapshot, restore, resume -------
    # The hard contract: the resumed run's per-request streams are
    # bit-identical to the uninterrupted baseline (ref above).
    import tempfile

    from repro.checkpoint import checkpoint
    from repro.runtime import faultinject as fi
    from repro.runtime.engine import DriftConfig, FaultConfig

    cfg_u, calib_u, calib_batch_u = plan_ctx["ffn_unchained"]
    preempt_step = max(1, ref.steps // 2)
    with tempfile.TemporaryDirectory() as td:
        e1 = Engine(cfg_u, params, ecfg, calib=calib_u)
        r1 = e1.run(trace, FaultConfig(
            injector=fi.FaultInjector([fi.PreemptAt(preempt_step)]),
            snapshot_dir=td))
        flat, snap_step = checkpoint.load_engine_snapshot(td)
        e2 = Engine(cfg_u, params, ecfg, calib=calib_u)
        e2.restore(flat)
        r2 = e2.resume()
    streams_match = all(
        a["tokens"] == b["tokens"]
        for a, b in zip(ref.requests, r2.requests))
    reasons_match = all(
        a["finish_reason"] == b["finish_reason"]
        and a["finished_step"] == b["finished_step"]
        for a, b in zip(ref.requests, r2.requests))
    emit("serving_crash_resume", 0.0,
         f"killed@{preempt_step}/{ref.steps} steps, resumed bit-identical="
         f"{streams_match}",
         data={
             "preempt_step": preempt_step,
             "baseline_steps": ref.steps,
             "preempted": r1.preempted,
             "snapshot_step": snap_step,
             "resumed_steps": r2.steps,
             "streams_match": streams_match,
             "finish_reasons_match": reasons_match,
             "compiled_steps_resumed": e2.compiled_steps(),
         })

    # --- drift + online recalibration: perturb device currents mid-trace;
    # the probe must flag it and hot-swap windows WITHOUT a third compiled
    # program (compiled_steps stays 2).
    drift_step = max(1, ref.steps // 3)
    e3 = Engine(cfg_u, params, ecfg, calib=calib_u)
    r3 = e3.run(trace, FaultConfig(
        injector=fi.FaultInjector(
            [fi.DriftAt(drift_step, sigma=0.5, repeats=3)]),
        drift=DriftConfig(probe_batch=calib_batch_u,
                          check_every=max(1, ref.steps // 4),
                          clip_threshold=0.01, window_tol=0.1)))
    emit("serving_drift_recalibration", 0.0,
         f"{len(r3.drift_events)} drift events, {r3.recalibrations} "
         f"recalibrations, compiled={r3.compiled_steps}",
         data={
             "drift_step": drift_step,
             "drift_events": len(r3.drift_events),
             "recalibrations": r3.recalibrations,
             "max_log_ratio": (r3.drift_events[0]["max_log_ratio"]
                               if r3.drift_events else 0.0),
             "max_clip_rate": (r3.drift_events[0]["max_clip_rate"]
                               if r3.drift_events else 0.0),
             "compiled_steps": r3.compiled_steps,
             "nan_logit_steps": r3.nan_logit_steps,
         })

    # --- SLA scheduling: priorities, deadline admission control, joule
    # budgets (runtime/sla.py priced by core.energy.serving_energy_model).
    from repro.runtime.sla import SlaConfig, min_steps_to_finish

    sla_cfg = SlaConfig(aging_steps=8)
    # Every base request: cycled priorities + a generously feasible
    # deadline (the engine drains the whole trace well inside 2x the
    # static-batch schedule) -> hit-rate must be exactly 1.0.
    feasible_deadline = 2 * static["wall_steps"] + 32
    sla_trace = [Request(r.rid, r.prompt, r.max_new_tokens, r.arrival_step,
                         priority=r.rid % 3,
                         deadline_steps=feasible_deadline)
                 for r in trace]
    # Deadline-infeasible: even immediate exclusive service needs
    # min_steps_to_finish steps; deadline 1 can never be met -> rejected
    # at admission, zero tokens, zero joules.
    infeasible = Request(900, prompt=trace[0].prompt, max_new_tokens=20,
                         deadline_steps=1)
    assert min_steps_to_finish(infeasible, ecfg.chunk) > 2
    # Joule-budgeted: enough for the prompt + ~2.5 tokens of its 6-token
    # budget -> admitted (min work fits) but finished over_budget
    # mid-stream.
    eng_sla = Engine(cfg_u, params, ecfg, calib=calib_u, sla=sla_cfg)
    e_tok = eng_sla.energy["energy_per_token_j"]
    budgeted = Request(901, prompt=trace[1].prompt, max_new_tokens=6,
                       joule_budget=(len(trace[1].prompt) + 2.5) * e_tok)
    rep_sla = eng_sla.run(sla_trace + [infeasible, budgeted])
    by_sla = {r["rid"]: r for r in rep_sla.requests}
    ref_by = {r["rid"]: r for r in ref.requests}
    # Request isolation survives SLA reordering: every base request's
    # stream is bit-equal to the plain-FIFO run's (itself proven
    # bit-identical to solo replays above).
    neighbors_ok = all(by_sla[r.rid]["tokens"] == ref_by[r.rid]["tokens"]
                       for r in trace)
    rej = by_sla[900]
    ob = by_sla[901]
    hit_denom = rep_sla.deadline_hits + rep_sla.deadline_misses
    hit_rate = rep_sla.deadline_hits / hit_denom if hit_denom else 0.0
    emit("serving_sla", 0.0,
         f"deadline_hit_rate={hit_rate:.2f}|rejected={rep_sla.rejected}"
         f"|over_budget={rep_sla.over_budget}",
         data={
             "aging_steps": sla_cfg.aging_steps,
             "feasible_deadline_steps": feasible_deadline,
             "deadline_hits": rep_sla.deadline_hits,
             "deadline_misses": rep_sla.deadline_misses,
             "deadline_hit_rate": hit_rate,
             "rejected": rep_sla.rejected,
             "rejected_zero_compute":
                 rej["finish_reason"] == "rejected"
                 and rej["tokens"] == [] and rej["joules_used"] == 0.0,
             "reject_reason": rej["reject_reason"],
             "over_budget": rep_sla.over_budget,
             "over_budget_partial_stream":
                 ob["finish_reason"] == "over_budget"
                 and 0 < len(ob["tokens"]) < budgeted.max_new_tokens,
             "over_budget_joules_used": ob["joules_used"],
             "over_budget_joule_budget": ob["joule_budget"],
             "neighbors_bit_equal_solo": neighbors_ok,
             "compiled_steps": rep_sla.compiled_steps,
         })

    # --- telemetry: rolling-median/MAD spike detection on step latency.
    # Warm the engine (jit-compile steps legitimately alert), then prove
    # the detector is quiet on a clean warm trace and fires EXACTLY once
    # on an injected straggler step.  All samples stream to the JSONL
    # artifact.
    from repro.runtime.telemetry import AlertRule, JsonlEmitter, MetricsSink

    Path(METRICS_JSONL).unlink(missing_ok=True)
    sink = MetricsSink(
        rules=[AlertRule("step_latency_s", kind="spike", k=6.0,
                         min_samples=6, abs_floor=0.05)],
        emitters=[JsonlEmitter(METRICS_JSONL)])
    e5 = Engine(cfg_u, params, ecfg, calib=calib_u, sink=sink)
    e5.run(trace)                         # warm (compile spikes expected)
    warm_alerts = len(sink.alerts)
    e5.run(trace)                         # clean warm run
    clean_fp = len(sink.alerts) - warm_alerts
    slow_step = max(1, ref.steps // 2)
    rep5 = e5.run(trace, FaultConfig(
        injector=fi.FaultInjector([fi.SlowStep(slow_step, sleep_s=0.3)])))
    injected = sink.alerts[warm_alerts + clean_fp:]
    for em in sink.emitters:
        em.close()
    emit("serving_telemetry_spike", 0.0,
         f"injected@{slow_step}: {len(injected)} alert(s), "
         f"clean_false_positives={clean_fp}",
         data={
             "slow_step": slow_step,
             "slow_sleep_s": 0.3,
             "clean_false_positives": clean_fp,
             "injected_alerts": len(injected),
             # the sink observes AFTER the tick lands, so the alert is
             # stamped at slow_step + 1
             "alert_at_injected_step":
                 len(injected) == 1 and injected[0].step == slow_step + 1,
             "alert_value_s": injected[0].value if injected else 0.0,
             "alert_limit_s": injected[0].limit if injected else 0.0,
             "sink_observations": sink.observations,
             "metrics_jsonl": METRICS_JSONL,
             "compiled_steps": rep5.compiled_steps,
         })

    # --- tracing & per-site attribution: a traced replay must be
    # bit-identical to the untraced reference, produce a schema-valid
    # Chrome trace whose request span boundaries match the report's finish
    # steps, and carry a per-site attribution table that sums bit-exactly
    # (left-to-right in table order) to the aggregate energy counters.
    from repro.runtime.trace import Tracer, validate_chrome_trace

    e6 = Engine(cfg_u, params, ecfg, calib=calib_u, tracer=Tracer())
    r6 = e6.run(trace)
    traced_streams_match = all(
        a["tokens"] == b["tokens"]
        and a["finish_reason"] == b["finish_reason"]
        and a["finished_step"] == b["finished_step"]
        for a, b in zip(ref.requests, r6.requests))
    counts = validate_chrome_trace(e6.tracer.chrome_trace())  # raises if bad
    summ = r6.trace_summary
    spans_match_report = all(
        summ["requests"][str(r["rid"])]["finished_step"]
        == r["finished_step"] for r in r6.requests)
    attr = r6.site_attribution
    ops_sum = e_sum = 0.0
    for srow in attr["per_site"].values():       # left-to-right, table order
        ops_sum += srow["ops"]
        e_sum += srow["energy_j"]
    site_sums_bit_exact = (
        ops_sum == r6.analog_ops and e_sum == r6.analog_energy_j
        and attr["fj_per_op"] == r6.fj_per_op
        and attr["tokens"] == r6.tokens_priced)
    attr_c = ch.site_attribution        # chained run: saved I/O per site
    emit("serving_trace", 0.0,
         f"{counts.get('B', 0)}B/{counts.get('E', 0)}E spans"
         f"|site_sums_exact={site_sums_bit_exact}",
         data={
             "traced_streams_match": traced_streams_match,
             "trace_event_counts": counts,
             "trace_ticks": summ["ticks"],
             "spans_match_report": spans_match_report,
             "site_sums_bit_exact": site_sums_bit_exact,
             "tokens_priced": r6.tokens_priced,
             "fj_per_op_by_site": {s: v["fj_per_op"]
                                   for s, v in attr["per_site"].items()},
             "chained_io_saved_j": attr_c["io_saved_j"],
             "chained_chains": attr_c["chains"],
             "compiled_steps": r6.compiled_steps,
         })

    # --- mesh scaling: DP slot-pool linearity + per-request bit-identity.
    # Runs in a subprocess with 4 forced host devices so this process keeps
    # its single-device jax runtime (same pattern as the multidev tests).
    import json as _json
    import os
    import subprocess
    import sys

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(repo / "src"), str(repo)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    child = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.bench_serving import _mesh_scaling_child; "
         f"_mesh_scaling_child({int(n_requests)})"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert child.returncode == 0, child.stderr[-3000:]
    line = [ln for ln in child.stdout.splitlines()
            if ln.startswith("MESH_RESULTS::")][0]
    mres = _json.loads(line.split("::", 1)[1])
    solo_m = mres.pop("solo")
    ref_streams = [{"rid": r["rid"], "tokens": r["tokens"],
                    "finish_reason": r["finish_reason"]}
                   for r in ref.requests]
    trivial = mres["1x1"]
    mesh_1x1_bit_identical = (
        trivial["streams"] == solo_m["streams"]
        and trivial["finished_steps"] == solo_m["finished_steps"]
        and trivial["steps"] == solo_m["steps"])
    per_request_ok = all(m["streams"] == ref_streams
                         for m in mres.values())
    slots_linear = all(m["total_slots"] == m["devices"] * ecfg.slots
                       for m in mres.values())
    emit("serving_mesh_scaling", 0.0,
         "tok/step " + "|".join(
             f"{k}={m['generated'] / max(m['steps'], 1):.2f}"
             for k, m in mres.items()),
         data={
             "slots_per_rank": ecfg.slots,
             "meshes": {k: {"devices": m["devices"],
                            "total_slots": m["total_slots"],
                            "wall_steps": m["steps"],
                            "generated_tokens": m["generated"],
                            "tokens_per_step":
                                m["generated"] / max(m["steps"], 1)}
                        for k, m in mres.items()},
             "solo_wall_steps": solo_m["steps"],
             "solo_matches_parent": solo_m["streams"] == ref_streams,
             "mesh_1x1_bit_identical": mesh_1x1_bit_identical,
             "per_request_bit_identity": per_request_ok,
             "slots_scale_linearly": slots_linear,
             "compiled_steps_by_mesh":
                 {k: m["compiled_steps"] for k, m in mres.items()},
         })

    from repro.kernels.tdvmm import ops as tdvmm_ops
    save_json("BENCH_serving.json",
              meta={"suite": "serving",
                    "autotune": tdvmm_ops.autotune_report()})


def _mesh_scaling_child(n_requests: int = 10) -> None:
    """Subprocess entry for the mesh-scaling row: replays the bench trace
    through the engine meshless and on (1,1)/(2,1)/(4,1) meshes.  Must run
    under ``--xla_force_host_platform_device_count=4`` (the parent sets it
    in the env before this interpreter starts, so it lands before the first
    jax import)."""
    import json

    from repro.launch.mesh import make_test_mesh
    from repro.runtime.paged_cache import pages_for

    base = smoke(get_config(ARCH))
    cfg = base.replace(tdvmm_plan=PLANS["ffn_unchained"])
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    calib_batch = {"inputs": jax.random.randint(
        jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)}
    calib = model.calibrate(params, calib_batch, cfg, max_len=32)
    trace = make_trace(cfg.vocab_size, n_requests=n_requests)
    max_len = max(len(r.prompt) + r.max_new_tokens for r in trace)
    ecfg = EngineConfig(slots=4, page_size=4, num_pages=64, chunk=8,
                        tile_n=64, max_pages_per_slot=pages_for(max_len, 4))

    def pack(rep):
        return {
            "steps": rep.steps, "devices": rep.devices,
            "total_slots": rep.total_slots,
            "generated": rep.generated_tokens,
            "compiled_steps": rep.compiled_steps,
            "streams": [{"rid": r["rid"], "tokens": r["tokens"],
                         "finish_reason": r["finish_reason"]}
                        for r in rep.requests],
            "finished_steps": [r["finished_step"] for r in rep.requests],
        }

    out = {"solo": pack(Engine(cfg, params, ecfg, calib=calib).run(trace))}
    for d, t in ((1, 1), (2, 1), (4, 1)):
        rep = Engine(cfg, params, ecfg, calib=calib,
                     mesh=make_test_mesh(d, t)).run(trace)
        out[f"{d}x{t}"] = pack(rep)
    print("MESH_RESULTS::" + json.dumps(out))


def check_invariants(doc: dict) -> None:
    """Assert the serving report's invariants (CI bench-smoke + run.py)."""
    rows = {r["name"]: r for r in doc["rows"]}
    engines = [r for n, r in rows.items() if n.startswith("serving_engine_")]
    assert len(engines) == 2, engines
    for r in engines:
        assert r["nan_logit_steps"] == 0, r          # evict-before-poison
        assert r["compiled_steps"] == 2, r           # two-compiled-step rule
        assert r["bit_identical_solo"], r            # request isolation
        assert r.get("timing_repeats", 0) >= 3, r    # median-of-repeats
        assert "timing_spread_us" in r, r            # spread recorded
    vs = rows["serving_vs_static"]
    assert vs["engine_beats_static_steps"], vs
    assert vs["engine_beats_static_utilization"], vs
    assert vs["paged_beats_dense_memory"], vs
    en = rows["serving_energy_chained_vs_unchained"]
    assert en["chained_saves_energy"], en
    cr = rows["serving_crash_resume"]
    assert cr["preempted"], cr                       # injection fired
    assert cr["streams_match"], cr                   # bit-identical resume
    assert cr["finish_reasons_match"], cr
    assert cr["compiled_steps_resumed"] <= 2, cr
    dr = rows["serving_drift_recalibration"]
    assert dr["recalibrations"] >= 1, dr             # drift caught + fixed
    assert dr["compiled_steps"] == 2, dr             # no third program
    sla = rows["serving_sla"]
    assert sla["deadline_hit_rate"] == 1.0, sla      # feasible trace: 100%
    assert sla["rejected"] >= 1, sla                 # infeasible rejected
    assert sla["rejected_zero_compute"], sla         # ...before any compute
    assert sla["over_budget"] >= 1, sla              # budget enforced
    assert sla["over_budget_partial_stream"], sla    # graceful degradation
    assert sla["neighbors_bit_equal_solo"], sla      # isolation under SLA
    assert sla["compiled_steps"] == 2, sla
    ts = rows["serving_telemetry_spike"]
    assert ts["clean_false_positives"] == 0, ts      # quiet when warm
    assert ts["injected_alerts"] == 1, ts            # exactly one spike
    assert ts["alert_at_injected_step"], ts          # at the right step
    assert ts["compiled_steps"] == 2, ts
    tr = rows["serving_trace"]
    assert tr["traced_streams_match"], tr            # tracing is pure
    assert tr["spans_match_report"], tr              # spans == finish steps
    assert tr["site_sums_bit_exact"], tr             # table sums == aggregate
    assert tr["chained_io_saved_j"] > 0.0, tr        # chain savings explicit
    assert tr["compiled_steps"] == 2, tr
    assert doc.get("autotune", {}).get("platform"), doc.get("autotune")
    ms = rows["serving_mesh_scaling"]
    assert set(ms["meshes"]) == {"1x1", "2x1", "4x1"}, ms
    assert ms["mesh_1x1_bit_identical"], ms          # (1,1) == no mesh exactly
    assert ms["per_request_bit_identity"], ms        # streams equal solo
    assert ms["solo_matches_parent"], ms             # runtime-independent
    assert ms["slots_scale_linearly"], ms            # DP pool: slots = dp * S
    for k, c in ms["compiled_steps_by_mesh"].items():
        assert c == 2, (k, c)                        # two programs per mesh


if __name__ == "__main__":
    run()
