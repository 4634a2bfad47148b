"""Per-phase attribution (``bench/scopes.py``) on hand-built traces with known
answers, and on a CPU profile of the engine at a test size."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import metrics, scopes, xplane  # noqa: E402

MS = 1_000_000
DEC, PRE = "jit_engine_decode", "jit_engine_prefill"
# What ``hlo_scopes`` would give for the instructions of these traces.
SCOPES = {DEC: {"while.1": None, "conditional.2": None, "gather.2": "kv.read",
                "fusion.1": "kv.write", "fusion.2": "kv.write", "fusion.3": "tdvmm",
                "fusion.4": "attention", "copy.5": "kv.read"},
          PRE: {"fusion.1": "attention"}}


def op(name, s, e, prog=DEC):
    return (name, s * MS, e * MS, prog)


def one_device(ops, modules=(), host=()):
    return {"host": [("bench_window", 0, 100 * MS)] + list(host),
            "devices": {"/device:TPU:0": {"ops": list(ops),
                                          "modules": list(modules)}}}


def ms(by_scope):
    return {k: round(v * 1e3, 6) for k, v in by_scope.items()}


# --- self time: containers count only what their nested ops leave ---------
@pytest.mark.parametrize("ops, want", [
    ([op("while.1", 0, 90), op("gather.2", 10, 40), op("fusion.3", 40, 80)],
     {None: 20, "kv.read": 30, "tdvmm": 40}),
    ([op("while.1", 0, 90), op("conditional.2", 10, 80), op("fusion.4", 20, 30)],
     {None: 80, "attention": 10}),
    ([op("fusion.1", 0, 10), op("fusion.2", 10, 25), op("fusion.3", 30, 40)],
     {"kv.write": 25, "tdvmm": 10}),
    ([op("while.1", 0, 90), op("fusion.9", 10, 20)],     # not in the map
     {None: 90}),
], ids=["while-holds-scoped-ops", "two-levels", "flat", "unknown-op"])
def test_self_time_excludes_nested_ops(ops, want):
    red = scopes.reduce(one_device(ops, [(DEC, 0, 95 * MS)]), SCOPES)
    assert ms(red["scoped"][DEC]) == want
    assert red["runs"] == {DEC: 1}


@pytest.mark.parametrize("ops, modules, want", [
    ([op("fusion.1", 10, 20, prog=None), op("fusion.1", 60, 70, prog=None)],
     [(DEC, 5, 30), (PRE, 50, 80)],
     {DEC: {"kv.write": 10}, PRE: {"attention": 10}}),
    ([op("fusion.1", 10, 20)], [], {DEC: {"kv.write": 10}}),
    ([op("fusion.1", 10, 20, prog=None)], [(DEC, 12, 30)], {None: {None: 10}}),
], ids=["program-by-time", "program-by-stat", "outside-any-program"])
def test_each_op_goes_to_its_program(ops, modules, want):
    mods = [(n, s * MS, e * MS) for n, s, e in modules]
    red = scopes.reduce(one_device(ops, mods), SCOPES)
    assert {p: ms(c) for p, c in red["scoped"].items()} == want


def test_ops_outside_the_window_and_per_run_time():
    ops = [op("gather.2", 10, 20), op("copy.5", 60, 66), op("gather.2", 98, 104)]
    mods = [(DEC, 5 * MS, 25 * MS), (DEC, 55 * MS, 70 * MS), (DEC, 97 * MS, 105 * MS)]
    red = scopes.reduce(one_device(ops, mods), SCOPES)
    assert red["runs"] == {DEC: 2}
    assert scopes.per_run_ms(red, DEC) == {"kv.read": pytest.approx(8.0)}
    assert scopes.per_run_ms(red, PRE) == {}


# --- an instruction's scope: its op_name path, read from the program's HLO ---
@pytest.mark.parametrize("path, want", [
    ("jit(engine_decode)/while/body/closed_call/kv.read/gather", "kv.read"),
    ("jit(engine_prefill)/head/tdvmm/weight_program/abs", "weight_program"),
    ("jit(engine_prefill)/head/rmsnorm", "head"),
    ("jit(engine_decode)/while/body/add", None),
    (None, None),
])
def test_innermost_scope_of_a_path(path, want):
    assert scopes.scope_of(path) == want


HLO = """HloModule jit_engine_decode, entry_computation_layout={...}

%fused_computation.1 (param_0: bf16[4,16]) -> bf16[4,16] {
  %param_0 = bf16[4,16]{1,0} parameter(0)
  ROOT %convert.3 = bf16[4,16]{1,0} convert(%param_0), metadata={op_name="jit(engine_decode)/kv.read/convert"}
}

%fused_computation.2 (param_0.1: bf16[4,16], param_1.1: bf16[4,16]) -> bf16[4] {
  %param_0.1 = bf16[4,16]{1,0} parameter(0)
  %param_1.1 = bf16[4,16]{1,0} parameter(1)
  %broadcast.5 = bf16[4,16]{1,0} broadcast(%param_1.1), dimensions={0,1}, metadata={op_name="jit(engine_decode)/while/body/attention/dot_general"}
  %multiply.6 = bf16[4,16]{1,0} multiply(%param_0.1, %broadcast.5)
  ROOT %reduce.7 = bf16[4]{0} reduce(%multiply.6, %constant.1), dimensions={1}, to_apply=%add
}

%fused_computation.3 (param_0.2: bf16[2,8,16], param_1.2: s32[]) -> bf16[1,8,16] {
  %param_0.2 = bf16[2,8,16]{2,1,0} parameter(0)
  %param_1.2 = s32[] parameter(1)
  %constant.2 = s32[] constant(0)
  ROOT %dynamic-slice.8 = bf16[1,8,16]{2,1,0} dynamic-slice(%param_0.2, %param_1.2, %constant.2, %constant.2), dynamic_slice_sizes={1,8,16}, metadata={op_name="jit(engine_decode)/while/body/dynamic_slice"}
}

%body.4 (arg_tuple.0: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %arg_tuple.0 = (s32[], bf16[8,16]{1,0}) parameter(0)
  %get-tuple-element.10 = bf16[8,16]{1,0} get-tuple-element(%arg_tuple.0), index=1
  %get-tuple-element.11 = s32[] get-tuple-element(%arg_tuple.0), index=0
  %scatter.12 = bf16[8,16]{1,0} scatter(%get-tuple-element.10, %get-tuple-element.11), metadata={op_name="jit(engine_decode)/while/body/kv.write/scatter"}
  %copy.13 = bf16[8,16]{0,1} copy(%scatter.12), metadata={op_name="jit(engine_decode)/while/body/dynamic_update_slice"}
  ROOT %tuple.14 = (s32[], bf16[8,16]{0,1}) tuple(%get-tuple-element.11, %copy.13)
}

ENTRY %main.9 (pool: bf16[8,16], t: s32[4]) -> bf16[4,16] {
  %pool = bf16[8,16]{1,0} parameter(0), metadata={op_name="pool"}
  %t = s32[4]{0} parameter(1), metadata={op_name="t"}
  %gte.1 = bf16[8,16]{1,0} get-tuple-element(%pool), index=0, metadata={op_name="jit(engine_decode)/while"}
  %copy.41 = bf16[8,16]{0,1} copy(%gte.1)
  %gather.2 = bf16[4,16]{1,0} gather(%copy.41, %t), slice_sizes={1,16}, metadata={op_name="jit(engine_decode)/while/body/kv.read/gather"}
  %copy.7 = bf16[4,16]{0,1} copy(%gather.2)
  %fusion.5 = bf16[4,16]{1,0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(engine_decode)/while/body/attention/dot_general"}
  %copy.8 = bf16[4,16]{0,1} copy(%fusion.5)
  %copy.9 = bf16[4,16]{1,0} copy(%copy.8)
  %convert_reduce_fusion.4 = bf16[4]{0} fusion(%copy.9, %gather.2), kind=kLoop, calls=%fused_computation.2
  %add.15 = bf16[4,16]{1,0} add(%copy.9, %copy.9), metadata={op_name="jit(engine_decode)/while/body/add"}
  %dynamic-slice_fusion.16 = bf16[1,8,16]{2,1,0} fusion(%gte.1, %t), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(engine_decode)/while/body/dynamic_slice"}
  %scatter.17 = bf16[1,8,16]{2,1,0} scatter(%dynamic-slice_fusion.16, %t), metadata={op_name="jit(engine_decode)/while/body/kv.write/scatter"}
  %while.18 = (s32[], bf16[8,16]{0,1}) while(%gte.1), condition=%cond.3, body=%body.4, metadata={op_name="jit(engine_decode)/while"}
  %get-tuple-element.19 = bf16[8,16]{0,1} get-tuple-element(%while.18), index=1
  ROOT %copy.20 = bf16[8,16]{1,0} copy(%get-tuple-element.19)
}
"""


@pytest.mark.parametrize("name, want", [
    ("gather.2", "kv.read"),       # its own op_name
    ("fusion.5", "attention"),     # a fusion: the op_name it carries
    ("convert.3", "kv.read"),      # inside a fused computation
    ("gte.1", "kv.read"),          # a mover with none upstream: its users
    ("copy.7", "kv.read"),         # inserted: its operand's scope
    ("copy.9", "attention"),       # through another inserted copy
    ("copy.41", "kv.read"),        # operands unscoped: its user's scope
    ("convert_reduce_fusion.4", "attention"),  # op_name dropped: what it fused
    ("add.15", None),              # computes: never inherits
    ("dynamic-slice_fusion.16", "kv.write"),   # a fusion that only moves data
    ("copy.20", "kv.write"),       # a loop's result: its body's root element
])
def test_hlo_scopes_of_inserted_and_fused_instructions(name, want):
    assert scopes.hlo_scopes(HLO)[name] == want


def test_trace_scoped_through_the_hlo_text():
    ops = [op("copy.41", 10, 30), op("gather.2", 30, 35), op("copy.9", 40, 50),
           op("add.15", 50, 52)]
    red = scopes.reduce(one_device(ops), {DEC: scopes.hlo_scopes(HLO)})
    assert ms(red["scoped"][DEC]) == {"kv.read": 25, "attention": 10, None: 2}


# --- idle gaps: the innermost host span ------------------------------------
TICK = [("tick", 0, 100 * MS), ("engine.admit", 1 * MS, 2 * MS),
        ("engine.assemble", 2 * MS, 5 * MS), ("engine.dispatch", 5 * MS, 8 * MS),
        ("engine.readback", 8 * MS, 70 * MS), ("engine.emit", 70 * MS, 74 * MS)]


@pytest.mark.parametrize("gap, spans, want", [
    ((60, 69), TICK, "engine.readback"),
    ((64, 74), TICK, "engine.readback"),    # 6 ms readback, 4 emit
    ((68, 74), TICK, "engine.emit"),        # 2 ms readback, 4 emit
    ((80, 95), TICK, "tick"),               # in the tick, after the engine's spans
    ((2, 7), TICK, "engine.assemble"),      # 3 ms assemble, 2 dispatch
    ((10, 20), [], "none"),
    ((10, 20), [("feed", 0, 12 * MS), ("tick", 12 * MS, 30 * MS)], "tick"),
], ids=["inside-readback", "mostly-readback", "mostly-emit", "tick-only",
        "several", "no-span", "flat-like-xplane"])
def test_gap_labelled_by_innermost_span(gap, spans, want):
    assert scopes.label_innermost((gap[0] * MS, gap[1] * MS), spans) == want


def test_idle_by_engine_span():
    ops = [op("fusion.1", 0, 8), op("fusion.2", 75, 100)]
    red = scopes.reduce(one_device(ops, host=TICK), SCOPES)
    # one gap, 8-75 ms: 62 ms under readback, 4 under emit, 1 under the tick
    assert red["idle_by_span"] == {"engine.readback": pytest.approx(0.067)}
    assert red["top_gaps"] == [["engine.readback", pytest.approx(0.067)]]


# --- the accepted reduction reads the same with engine spans present -------
def xplane_trace(engine_spans: bool) -> dict:
    """test_bench_xplane's trace, with the engine's spans inside each tick."""
    host = [("bench_window", 0, 100 * MS),
            ("tick", 0, 45 * MS), ("harvest", 45 * MS, 50 * MS),
            ("feed", 50 * MS, 60 * MS), ("tick", 60 * MS, 100 * MS)]
    if engine_spans:
        host += [("engine.admit", 0, 1 * MS), ("engine.assemble", 1 * MS, 4 * MS),
                 ("engine.dispatch", 4 * MS, 6 * MS),
                 ("engine.readback", 6 * MS, 40 * MS), ("engine.emit", 40 * MS, 45 * MS),
                 ("engine.admit", 60 * MS, 61 * MS), ("engine.dispatch", 61 * MS, 64 * MS),
                 ("engine.readback", 64 * MS, 97 * MS), ("engine.emit", 97 * MS, 100 * MS)]
    ops = [("tdvmm_fused_kernel.3", 5 * MS, 15 * MS), ("fusion.1", 15 * MS, 35 * MS),
           ("argmax", 36 * MS, 40 * MS),
           ("tdvmm_fused_kernel.7", 65 * MS, 75 * MS), ("fusion.1", 70 * MS, 95 * MS)]
    modules = [("jit_engine_decode", 5 * MS, 35 * MS), ("jit_argmax", 36 * MS, 40 * MS),
               ("jit_engine_decode", 65 * MS, 95 * MS)]
    return {"host": host, "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def record(engine_spans: bool) -> dict:
    red = xplane.reduce(xplane_trace(engine_spans))
    prog = xplane.program(red["modules"], 2)
    return {"trace": red, "decode_program": prog,
            "prefill_program": xplane.program(red["modules"], 1, prog),
            "least_kernel_s": 0.005, "kernel_launches": 2, "model_s": 0.001,
            "counts": {"decode_steps": 2, "active_slot_steps": 6.0}, "slots": 4}


@pytest.mark.parametrize("reader", ["idle_share", "decode_step_ms", "prefill_step_ms",
                                    "tdvmm_roofline", "mfu", "decode_occupancy"])
def test_accepted_readers_unchanged_by_engine_spans(reader):
    assert metrics.read(reader, record(True)) == metrics.read(reader, record(False))


def test_accepted_breakdown_unchanged_by_engine_spans():
    with_spans, without = record(True)["trace"], record(False)["trace"]
    assert with_spans == without


# --- a CPU profile of the engine: the scopes come from the programs' HLO ----
def test_cpu_profile_of_engine_ticks(tmp_path):
    import jax
    import numpy as np
    from repro.configs import TDVMMPlan, get_config, smoke, tdvmm_rule
    from repro.models import model
    from repro.runtime.engine import Engine, EngineConfig, Request
    from repro.runtime.trace import ENGINE_SPANS, STEP_SCOPES
    cfg = smoke(get_config("qwen1.5-0.5b")).replace(tdvmm_plan=TDVMMPlan(
        rules=(tdvmm_rule("ffn.*", enabled=True, backend="jnp"),)))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"inputs": jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                          cfg.vocab_size)}
    calib = model.calibrate(params, batch, cfg, max_len=48)
    ecfg = EngineConfig(slots=2, page_size=4, num_pages=16, chunk=8)
    eng = Engine(cfg, params, ecfg, calib=calib)
    eng.start([Request(rid=i, prompt=tuple(range(1, 6 + i)), max_new_tokens=5)
               for i in range(2)])
    for _ in range(3):                       # both prefills and a decode
        eng.tick()
    jax.block_until_ready(eng._st.caches)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("tick"):
                eng.tick()
        jax.block_until_ready(eng._st.caches)
    jax.profiler.stop_trace()
    tr = scopes.load(xplane.find_trace(str(tmp_path)))
    assert {n for n, _, _ in tr["host"]} == {xplane.WINDOW_SPAN, "tick", *ENGINE_SPANS}
    b, p = ecfg.slots, ecfg.resolved_max_pages
    shapes = {"inputs": jax.ShapeDtypeStruct((b, 1), np.int32),
              "block_tables": jax.ShapeDtypeStruct((b, p), np.int32),
              "pos": jax.ShapeDtypeStruct((b,), np.int32),
              "active": jax.ShapeDtypeStruct((b,), np.bool_)}
    text = eng._decode.lower(eng.params, shapes, eng._st.caches,
                             eng._windows).compile().as_text()
    hlo = scopes.hlo_scopes(text)
    ran = {o[0] for dev in tr["devices"].values() for o in dev["ops"]
           if o[3] == "jit_engine_decode"}
    assert ran and ran <= set(hlo), ran - set(hlo)    # the program that ran
    got = scopes.reduce(tr, {"jit_engine_decode": hlo})["scoped"]
    assert set(STEP_SCOPES) <= set(got["jit_engine_decode"])
    assert eng.compiled_steps() == 2


# --- the in-place row write after the layer scan --------------------------
# The shape of the row scatter of a prefill program on a v5e: a fusion with
# no op_name whose fused scatter and movers carry no scope, the pool
# (a parameter of the program) as its first argument, and the op_name
# left on the bitcast of its result.
SCATTER_HLO = """HloModule jit_engine_prefill, entry_computation_layout={...}

%region_18.61 (scatter.2: bf16[], scatter.3: bf16[]) -> bf16[] {
  %scatter.2 = bf16[] parameter(0), metadata={op_name="scatter"}
  ROOT %scatter.3 = bf16[] parameter(1), metadata={op_name="scatter"}
}

%fused_computation.6 (param_0.18: bf16[528512,1024], param_1.26: s32[4096], param_2.18: bf16[4096,1024]) -> bf16[528512,1024] {
  %param_0.18 = bf16[528512,1024]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.26 = s32[4096]{0:T(1024)S(1)} parameter(1)
  %reshape.670 = s32[8,512]{1,0:T(8,128)} reshape(%param_1.26)
  %transpose.116 = s32[8,512]{1,0:T(8,128)} transpose(%reshape.670), dimensions={0,1}
  %param_2.18 = bf16[4096,1024]{1,0:T(8,128)(2,1)S(1)} parameter(2)
  %reshape.671 = bf16[8,512,1024]{2,1,0:T(8,128)(2,1)} reshape(%param_2.18), metadata={op_name="jit(engine_prefill)/while" stack_frame_id=54}
  %transpose.117 = bf16[8,512,1024]{2,1,0:T(8,128)(2,1)} transpose(%reshape.671), dimensions={0,1,2}, metadata={op_name="jit(engine_prefill)/while" stack_frame_id=54}
  ROOT %scatter.4 = bf16[528512,1024]{1,0:T(8,128)(2,1)} scatter(%param_0.18, %transpose.116, %transpose.117), update_window_dims={2}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=2, to_apply=%region_18.61
}

%fused_computation.9 (param_0.30: bf16[64,1024], param_1.30: bf16[64,1024]) -> bf16[64,1024] {
  %param_0.30 = bf16[64,1024]{1,0} parameter(0)
  %param_1.30 = bf16[64,1024]{1,0} parameter(1)
  ROOT %scatter.9 = bf16[64,1024]{1,0} scatter(%param_0.30, %param_1.30, %param_1.30), to_apply=%region_18.61
}

ENTRY %main.1 (pool: bf16[8,4129,16,1024], rows: bf16[4096,1024], idx: s32[4096], t: bf16[64,1024]) -> bf16[8,4129,16,1024] {
  %pool = bf16[8,4129,16,1024]{3,2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="c['seg0']['k']"}
  %rows = bf16[4096,1024]{1,0:T(8,128)(2,1)} parameter(1)
  %idx = s32[4096]{0} parameter(2)
  %t = bf16[64,1024]{1,0} parameter(3)
  %bitcast.10 = bf16[528512,1024]{1,0:T(8,128)(2,1)} bitcast(%pool)
  %fusion.6 = bf16[528512,1024]{1,0:T(8,128)(2,1)} fusion(%bitcast.10, %idx, %rows), kind=kCustom, calls=%fused_computation.6, backend_config={"flag_configs":[],"aliasing_operands":{"lists":[{"indices":["0","3"]}]}}
  %bitcast.13 = bf16[8,4129,16,1024]{3,2,1,0:T(8,128)(2,1)} bitcast(%fusion.6), metadata={op_name="jit(engine_prefill)/kv.write/scatter" stack_frame_id=416}
  %fusion.9 = bf16[64,1024]{1,0} fusion(%t, %t), kind=kLoop, calls=%fused_computation.9
  ROOT %tuple.72 = (bf16[8,4129,16,1024]{3,2,1,0:T(8,128)(2,1)}, bf16[64,1024]{1,0}) tuple(%bitcast.13, %fusion.9)
}
"""


@pytest.mark.parametrize("name, want", [
    ("fusion.6", "kv.write"),      # scatter fusion: along its pool, then its users
    ("bitcast.10", None),          # the pool's bitcast: its user computes
    ("fusion.9", None),            # a scatter with nothing scoped around it
])
def test_scatter_fusion_takes_the_scope_along_its_pool(name, want):
    assert scopes.hlo_scopes(SCATTER_HLO)[name] == want


# --- the readers of the scopes and of the engine's counters ----------------
def scoped_record() -> dict:
    """Two runs of the decode program and one of the prefill program in a
    100 ms window, scoped through a map like ``hlo_scopes`` gives."""
    hlo = {DEC: {"gather.2": "kv.read", "fusion.1": "kv.write",
                 "fusion.7": "weight_program", "fusion.3": "tdvmm"},
           PRE: {"fusion.1": "attention", "fusion.7": "weight_program",
                 "fusion.8": None}}
    ops = [op("gather.2", 0, 10), op("fusion.1", 10, 12), op("fusion.7", 12, 15),
           op("fusion.3", 15, 20),
           op("gather.2", 30, 38), op("fusion.1", 38, 40), op("fusion.7", 40, 43),
           op("fusion.3", 43, 50),
           op("fusion.1", 60, 80, PRE), op("fusion.7", 80, 90, PRE), op("fusion.8", 90, 91, PRE)]
    mods = [(DEC, 0, 20 * MS), (DEC, 30 * MS, 50 * MS), (PRE, 60 * MS, 91 * MS)]
    red = scopes.reduce(one_device(ops, mods), hlo)
    return {"main_program": DEC, "scopes": {p: scopes.per_run_ms(red, p) for p in red["runs"]},
            "counts": {"kv_pages_read": 400, "kv_pages_live": 84}}


@pytest.mark.parametrize("reader, main, want", [
    ("step_kv_ms", DEC, 11.0),        # (10 + 2 + 8 + 2) ms over two runs
    ("step_wprog_ms", DEC, 3.0),
    ("step_kv_ms", PRE, None),        # no KV scope in that program
    ("step_wprog_ms", PRE, 10.0),
    ("step_kv_ms", None, None),       # no step program in the window
    ("step_wprog_ms", None, None),
])
def test_step_scope_readers(reader, main, want):
    rec = dict(scoped_record(), main_program=main)
    got = metrics.read(reader, rec)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("counts, want", [
    ({"kv_pages_read": 400, "kv_pages_live": 84}, 21.0),
    ({"kv_pages_read": 0, "kv_pages_live": 0}, None),
])
def test_kv_live_share_reader(counts, want):
    got = metrics.read("kv_live_share", dict(scoped_record(), counts=counts))
    assert got == (None if want is None else pytest.approx(want))
