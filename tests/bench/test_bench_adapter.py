"""The engine adapter on a small configuration on the CPU (add, tick,
harvest), the benchmark's weights and reference against the program, and
the control at a test size."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from benchcells import (CHECK_LIMITS, ROOT, check_size, no_compile_cache,  # noqa: F401
                        run_cell, tiny)
from bench import adapter, harness, reference, weights  # noqa: E402


def build(tied: bool):
    from repro.models import model
    from repro.runtime.engine import Engine
    _, cfg, mix = tiny(tied)
    mcfg = harness.model_config(cfg)
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
    mcfg = harness.model_config(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    params = weights.make_params(shapes, 2**35 + 1)
    ref = reference.Reference(cfg, 2**35 + 1)
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
    ref = reference.Reference(cfg, 3)
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
