"""One run of one cell: set-up, measured window, check, result line.

``run`` drives the serving engine (``runtime/engine.py``) through
``adapter.Driver`` with the cell's traffic, for ``seconds`` seconds, then
checks what the window served against the plain reference of the
configuration's architecture (``archs/<name>/``, which alone knows one)
and returns the result line.  ``main`` (``run.py``) refuses to run
without a chip; tests call ``run`` on the CPU at a small size.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import archs

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_traces"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell_parts(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(manifest, cell, configuration, traffic mix) of a workload, by name."""
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = load_json(root / conf["file"])
    archs.find(cfg, conf["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return manifest, cell, cfg, mix


def check_layout(shapes, cfg: dict) -> None:
    """The program's parameter tree must be the layout the reference draws."""
    import jax
    from bench import weights
    got = {weights.path_str(p): tuple(s.shape)
           for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    want = archs.find(cfg).reference.param_shapes(cfg)
    if got != want:
        raise SystemExit(f"bench: the program's parameter tree differs from "
                         f"the reference's layout: {sorted(set(got.items()) ^ set(want.items()))}")


def calibrate(params, tokens, mcfg):
    """The program's calibration capture (``calibration.collect`` around
    its ``prefill_step``, as ``models.model.calibrate`` does) on the seeded
    batch, compiled as one program: run eagerly, the weight programming of
    a 152064-wide analog head alone holds several float32 copies of it."""
    import jax
    import jax.numpy as jnp
    from repro.core import calibration
    from repro.models import model
    rows, length = tokens.shape
    caches = model.init_caches(mcfg, rows, length)
    step = jax.jit(lambda p, b, c: model.prefill_step(p, b, c, mcfg)[0])
    with calibration.collect() as got:
        jax.block_until_ready(step(params, {"inputs": jnp.asarray(tokens)}, caches))
    return calibration.CalibrationState.from_collected(got)


def engine_config(mix: dict):
    from repro.runtime.engine import EngineConfig
    from repro.runtime.paged_cache import pages_for
    e = mix["engine"]
    per_slot = pages_for(e["max_context"], e["page_size"])
    return EngineConfig(slots=e["slots"], page_size=e["page_size"],
                        chunk=e["chunk"], num_pages=e["slots"] * per_slot,
                        max_pages_per_slot=per_slot)


@dataclasses.dataclass
class Req:
    """What the window knows of one request."""
    prompt: tuple
    served: int = 0
    reason: str | None = None


class CompileCount:
    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_):
        if name.endswith("backend_compile_duration"):
            self.n += 1


def warm_up(drv, mix: dict, vocab: int) -> None:
    """Compile both step programs (and the per-step host-side ops) at the
    cell's shapes: one request of two prefill chunks and two tokens."""
    from repro.runtime.engine import Request
    e = mix["engine"]
    n = min(e["chunk"] + 1, e["max_context"] - 2)
    drv.start()
    drv.add([Request(rid=-1, prompt=tuple(range(1, n + 1)), max_new_tokens=2)])
    while drv.tick():
        pass
    drv.sync()


class Window:
    """The measured window: keeps the engine's queue at twice its slots
    from the stream, and harvests what it served."""

    def __init__(self, drv, stream, mix: dict, vocab: int):
        self.drv, self.stream, self.vocab = drv, stream, vocab
        self.slots = mix["engine"]["slots"]
        self.reqs: dict[int, Req] = {}
        self.tracked: set[int] = set()
        self.next = 0
        self.finished_at: dict[int, float] = {}

    def feed(self) -> None:
        from repro.runtime.engine import Request
        while self.drv.pending() < 2 * self.slots:
            it = self.stream.item(self.next)
            prompt = self.stream.prompt(it, self.vocab)
            self.reqs[self.next] = Req(prompt)
            self.drv.add([Request(rid=self.next, prompt=prompt,
                                  max_new_tokens=it.output_len)])
            self.next += 1

    def harvest(self, now: float) -> None:
        for rid, _, _ in self.drv.occupied():
            if rid >= 0:
                self.tracked.add(rid)
        for rid in list(self.tracked):
            tokens, reason = self.drv.record(rid)
            r = self.reqs[rid]
            r.served = len(tokens)
            if reason is not None:
                r.reason = reason
                self.finished_at[rid] = now
                self.tracked.discard(rid)


def positions(drv) -> dict[int, int]:
    return {rid: pos for rid, _, pos in drv.occupied()}


def ranges(w: Window, p0: dict, p1: dict, t0: float, t1: float) -> list[tuple[int, int]]:
    """The positions ``start <= p < end`` each request processed between two
    snapshots."""
    out = []
    for rid, r in w.reqs.items():
        end = p1.get(rid)
        if end is None:
            done = w.finished_at.get(rid)
            if done is None or not (t0 <= done <= t1):
                continue
            end = len(r.prompt) + r.served - 1
        out.append((p0.get(rid, 0), end))
    return out


def sample(w: Window, seed: int, target: int, most: int) -> list[int]:
    """Finished requests to check: the one with most served tokens, then a
    seeded draw from the next earliest ``4 * most`` finished ones, until
    ``target`` tokens or ``most`` requests.  The pool does not depend on
    how many requests the window happened to finish."""
    done = sorted(rid for rid, r in w.reqs.items()
                  if r.reason in ("max_tokens", "eos"))
    if not done:
        return []
    first = max(done, key=lambda rid: (w.reqs[rid].served, -rid))
    pool = [rid for rid in done if rid != first][:4 * most]
    rng = np.random.default_rng([int(seed) % (1 << 64), 0x5A3])
    out, tokens = [], 0
    for rid in [first] + [pool[i] for i in rng.permutation(len(pool))]:
        if tokens >= target or len(out) >= most:
            break
        out.append(rid)
        tokens += w.reqs[rid].served
    return out


def check(cfg: dict, mix: dict, seed: int, served: list[tuple], cal_tokens,
          control: bool = False) -> dict:
    """Run the reference over each (prompt, served tokens) and read, at
    every served position, how far the served token's logit lies below the
    reference's best.  With ``control``, also the control's reading: the
    same gap for the token that the reference in float8 puts first."""
    import jax
    import jax.numpy as jnp
    reference = archs.find(cfg).reference
    seqs = [np.asarray(p + tuple(t[:-1]), np.int32) for p, t in served]
    starts = [len(p) - 1 for p, _ in served]
    rows = mix["output"]["max"]
    max_len = mix["engine"]["max_context"]
    probes = {"": [np.asarray(t, np.int32) for _, t in served]}
    with jax.default_matmul_precision("highest"):
        if control:
            low = reference.Reference(cfg, seed, "fp8")
            low.calibrate(cal_tokens)
            probes["control_"] = low.served_logits(
                seqs, starts, max_len, rows,
                lambda j, lg: np.asarray(jnp.argmax(lg[:len(served[j][1])], -1)))
            del low
            gc.collect()
        t = time.perf_counter()
        ref = reference.Reference(cfg, seed)
        ref.calibrate(cal_tokens)
        log(f"reference weights and calibration {time.perf_counter() - t:.2f} s")

        def gaps(j, logits):
            out = {}
            for name, toks in probes.items():
                t = jnp.asarray(toks[j])
                lg = logits[:len(t)]
                out[name] = np.asarray(jnp.max(lg, -1) - lg[jnp.arange(len(t)), t])
            return out
        per = ref.served_logits(seqs, starts, max_len, rows, gaps)
    res = {"tokens": sum(len(p[""]) for p in per), "requests": len(per)}
    for name in probes:
        g = np.concatenate([p[name] for p in per]) if per else np.zeros(0)
        res[name + "max_logit_gap"] = float(g.max()) if g.size else math.nan
        res[name + "mean_logit_gap"] = float(g.mean()) if g.size else math.nan
    return res


def run(cell: dict, cfg: dict, mix: dict, manifest: dict, seed: int,
        seconds: float, trace: bool, t_start: float, control: bool = False) -> dict:
    """One run; returns the result line as a dict."""
    import jax
    from bench import adapter, scopes, traffic, weights, work, xplane
    from repro.kernels.tdvmm import ops as tdvmm_ops
    from repro.models import model
    from repro.runtime.engine import Engine

    dev = jax.devices()
    compiles = CompileCount()
    mcfg = archs.find(cfg).program.model_config(cfg)
    vocab = mcfg.vocab_size
    t = time.perf_counter()
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    check_layout(shapes, cfg)
    params = weights.make_params(shapes, seed)
    jax.block_until_ready(params)
    log(f"weights {mcfg.param_count() / 1e6:.0f}M params in "
        f"{time.perf_counter() - t:.2f} s")
    c = cfg["calibration"]
    cal_tokens = weights.calibration_tokens(seed, vocab, c["rows"], c["length"])
    t = time.perf_counter()
    calib = calibrate(params, cal_tokens, mcfg)
    log(f"calibrate {len(calib.windows)} sites in {time.perf_counter() - t:.2f} s")
    ecfg = engine_config(mix)
    engine = Engine(mcfg, params, ecfg, calib=calib)
    drv = adapter.Driver(engine)
    t = time.perf_counter()
    warm_up(drv, mix, vocab)
    log(f"warm-up (both steps) {time.perf_counter() - t:.2f} s, "
        f"compiled steps {drv.compiled_steps()}, backend compiles {compiles.n}")

    stream = traffic.Stream(mix, seed)
    w = Window(drv, stream, mix, vocab)
    drv.start()
    # The window opens once every slot is occupied and decoding.
    while True:
        w.feed()
        drv.tick()
        w.harvest(time.perf_counter())
        if drv.counts().decode_steps and len(drv.occupied()) == w.slots:
            break
    drv.sync()
    n_compiles = compiles.n
    c0 = drv.counts()
    t_open = time.perf_counter()
    setup_s = time.time() - t_start
    log(f"setup {setup_s:.2f} s; window opens")

    lead = min(mix["trace"]["lead_s"], 0.3 * seconds)
    span_s = min(mix["trace"]["seconds"], 0.5 * seconds)
    tracing, traced = None, None
    while True:
        now = time.perf_counter()
        if now - t_open >= seconds:
            break
        if trace and traced is None and tracing is None and now - t_open >= lead:
            drv.sync()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # host spans only, no call tree
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            ann = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            ann.__enter__()
            tracing = {"ann": ann, "t0": time.perf_counter(), "c0": drv.counts(),
                       "p0": positions(drv)}
        if tracing is not None and now - tracing["t0"] >= span_s:
            drv.sync()
            tracing["t1"] = time.perf_counter()
            tracing["c1"] = drv.counts()
            tracing["p1"] = positions(drv)
            tracing["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced, tracing = tracing, None
        with jax.profiler.TraceAnnotation("feed"):
            w.feed()
        with jax.profiler.TraceAnnotation("tick"):
            drv.tick()
        with jax.profiler.TraceAnnotation("harvest"):
            w.harvest(time.perf_counter())
    drv.sync()
    window_s = time.perf_counter() - t_open
    c1 = drv.counts()
    d = c1.minus(c0)
    window_compiles = compiles.n - n_compiles
    compiled_steps = drv.compiled_steps()
    log(f"window {window_s:.3f} s: {d.prefill_steps} prefill chunks, "
        f"{d.decode_steps} decode steps, {d.prompt_tokens} prompt + "
        f"{d.generated_tokens} generated tokens, backend compiles "
        f"{window_compiles}, compiled steps {compiled_steps}; at the close "
        f"{drv.pending()} queued, {len(drv.occupied())} in slots")
    at = tdvmm_ops.autotune_report()
    log(f"autotune: {len(at['entries'])} launch shapes, "
        f"{len(at['misses'])} misses")

    stats = dev[0].memory_stats() or {}
    peak = max(int((x.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for x in dev)
    log(f"memory: peak {peak} bytes; bytes_limit "
        f"{stats.get('bytes_limit', 'not reported')}")

    # --- end-to-end metrics (host clock) ------------------------------
    attempted = len(w.reqs)
    failed = sum(1 for r in w.reqs.values() if r.reason in ("failed", "evicted"))
    e2e = {"tok_s": (d.prompt_tokens + d.generated_tokens) / window_s,
           "setup_s": setup_s}

    # --- per-layer record (traced sub-window) --------------------------
    record = None
    if traced is not None:
        pk = work.peaks(dev[0].device_kind)
        tdc = traced["c1"].minus(traced["c0"])
        path = xplane.find_trace(str(TRACE_DIR))
        tr = xplane.load(path)
        log("trace device planes (op events): "
            + ", ".join(f"{k} ({len(v['ops'])})" for k, v in tr["devices"].items()))
        red = xplane.reduce(tr)
        t, n0 = time.perf_counter(), compiles.n
        hlo = {p: scopes.hlo_scopes(text) for p, text in drv.step_hlo().items()}
        if compiles.n > n0:
            # Not found in the cache: another lowering, whose instruction
            # names would put the trace's ops under the wrong scopes.
            log(f"step programs' HLO: {compiles.n - n0} compiles; no scope is read")
            hlo = {}
        att = scopes.reduce(scopes.load(path), hlo)
        log(f"step programs' HLO and scope reduction {time.perf_counter() - t:.2f} s")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        e = mix["engine"]
        for site, g, m, k, n, cnt in work.window_launches(cfg, 1, e["slots"], 1, e["chunk"]):
            least, inten, bound = work.launch_cost(g, m, k, n, pk)
            log(f"launch {site} (G,M,K,N)=({g},{m},{k},{n}) x{cnt}: {inten:.1f} ops/byte, "
                f"bound by {bound}, least {least * 1e6:.2f} us")
        decode = xplane.program(red["modules"], tdc.decode_steps)
        prefill = xplane.program(red["modules"], tdc.prefill_steps, decode)
        main = max((p for p in (decode, prefill) if p), key=lambda p: p[2], default=None)
        record = {
            "cfg": cfg, "mix": mix, "peaks": pk, "trace": red,
            "counts": dict(tdc), "slots": e["slots"],
            "chunk": e["chunk"], "host": e2e, "decode_program": decode,
            "prefill_program": prefill,
            "main_program": main and scopes.program_name(main[0]),
            "scopes": {p: scopes.per_run_ms(att, p) for p in att["runs"] if p in hlo},
            "least_kernel_s": work.least_kernel_seconds(
                cfg, pk, tdc.decode_steps, e["slots"], tdc.prefill_steps, e["chunk"]),
            "kernel_launches": work.kernel_launches(
                cfg, tdc.decode_steps, e["slots"], tdc.prefill_steps, e["chunk"]),
            "model_s": work.model_seconds(
                cfg, pk, tdc.prompt_tokens + round(tdc.active_slot_steps),
                tdc.generated_tokens, ranges(w, traced["p0"], traced["p1"],
                                             traced["t0"], traced["t1"])),
        }
        log(f"trace: window {red['window_s']:.4f} s, busy {red['busy_s']:.4f} s, "
            f"programs decode {record['decode_program']} prefill "
            f"{record['prefill_program']}; idle by host span {att['idle_by_span']}")
        for p, ms in record["scopes"].items():
            log(f"self ms per run of {p}: {ms}")
        for text, sec in red["top_ops"]:
            log(f"device op {sec * 1e3:.3f} ms: {text}")
        for name, (n, sec) in sorted(red["ops"].items()):
            if "kernel" in name:
                log(f"kernel op {name}: {n} events, {sec * 1e3:.3f} ms")

    # --- correctness: the served tokens against the reference ----------
    ids = sample(w, seed, mix["check"]["tokens"], mix["check"]["requests"])
    served = [(w.reqs[rid].prompt, list(drv.record(rid)[0])) for rid in ids]
    nan_steps = c1.nan_steps
    del engine, drv, params, calib, w
    gc.collect()
    t = time.perf_counter()
    got = check(cfg, mix, seed, served, cal_tokens, control)
    log(f"reference: {got['requests']} requests, {got['tokens']} served tokens "
        f"in {time.perf_counter() - t:.2f} s; {got}")
    if control:
        # The control's tokens stand in the program's place, through the
        # same checks.
        got.update({name: got[f"control_{name}"] for name in cfg["correct"]})
    checks = {name: (got[name], limit, got[name] <= limit)
              for name, limit in cfg["correct"].items()}
    checks.update({
        "checked_tokens": (got["tokens"], mix["check"]["tokens"] // 2,
                           got["tokens"] >= mix["check"]["tokens"] // 2),
        "failed_requests": (failed, 0, failed == 0),
        "nan_steps": (nan_steps, 0, nan_steps == 0),
        "compiled_steps": (compiled_steps, 2, compiled_steps == 2),
        "window_compiles": (window_compiles, 0, window_compiles == 0),
    })
    correct = all(ok for _, _, ok in checks.values())

    names = [m["name"] for m in manifest["end_to_end"]
             if cell["name"] in m.get("workloads", [cell["name"]])]
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    metrics = {}
    if record is None:
        for n in names:
            metrics[n] = {"value": e2e[n], "unit": units[n]}
    else:
        from bench import metrics as readers
        for m in manifest["per_layer"]:
            if cell["name"] not in m.get("workloads", []):
                continue
            v = readers.read(m["name"], record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if record is not None:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        out["breakdown"] = {"device_ops": record["trace"]["top_ops"],
                            "idle_gaps": att["top_gaps"]}
    for name, (v, lim, ok) in checks.items():
        log(f"check {name}: {v} (limit {lim}) {'ok' if ok else 'FAILED'}")
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, (v, lim, ok) in checks.items()}
    return out
