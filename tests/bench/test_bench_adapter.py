"""The engine adapter on a small configuration on the CPU (add, tick,
harvest), the benchmark's weights and reference against the program, and
the control at a test size."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from benchcells import (CHECK_LIMITS, ROOT, check_size, no_compile_cache,  # noqa: F401
                        run_cell, tiny)
from bench import adapter, archs, harness, weights  # noqa: E402


def build(tied: bool):
    from repro.models import model
    from repro.runtime.engine import Engine
    _, cfg, mix = tiny(tied)
    mcfg = archs.find(cfg).program.model_config(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    harness.check_layout(shapes, cfg)
    params = weights.make_params(shapes, 3)
    cal = weights.calibration_tokens(3, mcfg.vocab_size, 2, 32)
    calib = model.calibrate(params, {"inputs": jax.numpy.asarray(cal)}, mcfg,
                            max_len=32)
    eng = Engine(mcfg, params, harness.engine_config(mix), calib=calib)
    return cfg, mix, mcfg, params, eng


def test_add_tick_harvest(no_compile_cache):
    from repro.runtime.engine import Request
    _, mix, mcfg, _, eng = build(True)
    drv = adapter.Driver(eng)
    drv.start()
    assert not drv.tick()                      # nothing queued: idle
    drv.add([Request(rid=i, prompt=tuple(range(1, 20 + i)), max_new_tokens=3)
             for i in range(6)])
    assert drv.pending() == 6
    assert drv.tick()                          # admits four, prefills one
    occ = drv.occupied()
    assert [rid for rid, _, _ in occ] == [0, 1, 2, 3] and drv.pending() == 2
    while drv.tick():
        pass
    c = drv.counts()
    assert c.generated_tokens == 18 and c.prompt_tokens == sum(19 + i for i in range(6))
    assert c.decode_steps > 0 and c.active_slot_steps == pytest.approx(12)
    for i in range(6):
        toks, reason = drv.record(i)
        assert len(toks) == 3 and reason == "max_tokens"
    assert drv.compiled_steps() == 2


def test_weights_drawn_again_leaf_by_leaf(no_compile_cache):
    _, cfg, _ = tiny(False)
    from repro.models import model
    mcfg = archs.find(cfg).program.model_config(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    params = weights.make_params(shapes, 2**35 + 1)
    ref = archs.find(cfg).reference.Reference(cfg, 2**35 + 1)
    flat = {weights.path_str(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert set(flat) == set(ref.w)
    for path, v in flat.items():
        assert np.array_equal(np.asarray(v), np.asarray(ref.w[path])), path


def test_reference_windows_match_program_calibration(no_compile_cache):
    """The reference calibrates on its own; the windows it finds are the
    program's within a few percent (a window is a maximum over every layer,
    and bfloat16 rounding inside XLA's own expansions, such as that of
    ``logistic``, differs from the reference's by an ulp here and there)."""
    cfg, _, mcfg, params, eng = build(False)
    ref = archs.find(cfg).reference.Reference(cfg, 3)
    with jax.default_matmul_precision("highest"):
        got = ref.calibrate(weights.calibration_tokens(3, mcfg.vocab_size, 2, 32))
    prog = {k: np.asarray(v) for k, v in eng.calib.windows.items()}
    assert set(got) == set(prog)
    for site in got:
        np.testing.assert_allclose(got[site], prog[site], rtol=0.1, err_msg=site)


@pytest.mark.parametrize("seed", [2**33 + 5, 2**40 + 17])
@pytest.mark.parametrize("tied", [True, False], ids=["tied-head", "analog-head"])
def test_control_reads_far_above_the_program(tied, seed, monkeypatch,
                                             no_compile_cache):
    """One window, read twice: the program's served tokens pass every
    compared number; the control's (the reference in float8 in the
    program's place), sent through the same checks, fails one of them."""
    readings = []
    real = harness.check

    def check(*a, **k):
        readings.append(real(*a, **k))
        return dict(readings[-1])

    monkeypatch.setattr(harness, "check", check)
    out = run_cell(check_size(tied), seed, control=True)
    limits = CHECK_LIMITS[tied]
    assert all(readings[0][n] <= lim for n, lim in limits.items()), readings
    assert not out["correct"]
    assert any(out["checks"][n]["value"] > lim for n, lim in limits.items())
    assert all(out["checks"][n]["value"] == readings[0][f"control_{n}"] for n in limits)


def test_a_counter_added_to_run_state_reaches_counts_and_minus():
    """``Counts`` holds every numeric field of the engine's ``RunState``, so a
    counter the engine adds reaches the readers with no change to the
    adapter: here a copy of ``RunState`` with one more field."""
    import dataclasses
    from types import SimpleNamespace
    from repro.runtime.engine import RunState
    state = dataclasses.make_dataclass(
        "RunState", [("routed_rows", int, dataclasses.field(default=0))],
        bases=(RunState,))
    st = state(requests=[], records={}, sched=None, pool=None, caches=None)
    eng = SimpleNamespace(_st=st, total_slots=4, _run_compiled=None)
    drv = adapter.Driver(eng)
    c0 = drv.counts()
    st.routed_rows, st.kv_pages_read, st.kv_pages_live = 7, 30, 12
    st.decode_steps, st.util_samples = 3, [0.5, 1.0, 0.75]
    d = drv.counts().minus(c0)
    assert {"routed_rows", "kv_pages_read", "kv_pages_live", "decode_steps",
            "active_slot_steps", "wall_s"} <= set(d)
    assert not {"preempted", "snapshot_path", "util_samples", "caches"} & set(d)
    assert (d.routed_rows, d["kv_pages_read"], d.kv_pages_live) == (7, 30, 12)
    assert d.decode_steps == 3 and d.active_slot_steps == pytest.approx(9.0)
    with pytest.raises(AttributeError):
        d.no_such_counter


@pytest.fixture
def compile_cache(tmp_path):
    """A persistent compilation cache of this test's own, as ``run.py``
    keeps one, that holds every program."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = {n: getattr(jax.config, n) for n in names}
    cc.reset_cache()
    for n, v in zip(names, (True, str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    yield
    cc.reset_cache()
    for n, v in old.items():
        jax.config.update(n, v)


def test_step_hlo_is_the_program_that_ran(compile_cache):
    """The optimised HLO of each step program the engine ran, keyed by the
    name its events carry in a profile, with the step scopes in it.  Its
    lowering is the call's own, so it is found in the compilation cache:
    nothing compiles again (a program compiled from another lowering would
    name its instructions otherwise)."""
    from repro.runtime.engine import Request
    from repro.runtime.trace import STEP_SCOPES
    from bench import scopes
    _, _, _, _, eng = build(True)
    drv = adapter.Driver(eng)
    drv.start()
    drv.add([Request(rid=0, prompt=tuple(range(1, 20)), max_new_tokens=3)])
    while drv.tick():
        pass
    compiles = harness.CompileCount()
    hlo = drv.step_hlo()
    assert compiles.n == 0
    assert set(hlo) == {"jit_engine_prefill", "jit_engine_decode"}
    for text in hlo.values():
        assert {"kv.read", "kv.write", "tdvmm"} <= set(scopes.hlo_scopes(text).values())
        assert set(scopes.hlo_scopes(text).values()) <= set(STEP_SCOPES) | {None}
    assert drv.compiled_steps() == 2
