"""Trinity-Mini's layer (AFMoE) against its plain reference, on the CPU.

The program runs in float32 on the benchmark's seeded weights (drawn in
bfloat16, as the reference draws them), every analog site on; the
reference (``bench/archs/afmoe/reference.py``) runs the same sites in
float32 on whole sequences.  Both calibrate their own readout windows on
the same batch, and in float32 they find the same ones.

What the engine serves (prefill chunks, then decode, through the window
rings and the full pool) has to give the reference's logits, on average
over every position it computes, within ``TOL``.  Not exactly: float32
sums in another order (XLA fuses the jitted reference differently, the
ring gathers keys in ring order) now and then land a value on the other
side of a 6-bit code boundary, and the moved code moves the logits of
that position and of the positions that attend to it (read at this
size: mean |difference| 0.016, 34% of positions above 0.05, largest
0.88).  Computing the experts' sites without their codes, leaving out the
gate or the QK-norm, widening the window or rotating the full layers
moves every position (mean 0.15 to 0.76).
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import archs, harness, weights  # noqa: E402
from repro.configs import TDVMMPlan, tdvmm_rule  # noqa: E402
from repro.models import attention, model, moe  # noqa: E402
from repro.runtime.engine import Engine, EngineConfig, Request  # noqa: E402

SEED = 2**35 + 11
# Mean |program - reference| logit over the served positions: a sound
# program reads 0.016 here, each fault below 0.15 or more.
TOL = 0.05
WINDOW, CHUNK, PAGE = 8, 8, 4


def tiny_cfg() -> dict:
    """The benchmark's configuration file at a test size: 4 layers (2
    dense, window, window | 1 MoE window | 1 MoE full), 4 of 8 experts held,
    top-2, one shared expert, an 8-position window."""
    cfg = json.loads((ROOT / "bench/configs/trinity-mini-1chip.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=4, vocab_size=500, num_experts=4,
               num_experts_per_tok=2, sliding_window=WINDOW, expert_offset=0)
    cfg["published"] = {"num_hidden_layers": 32, "num_experts": 8}
    return cfg


def program(cfg: dict, **changes):
    """(float32 ModelConfig, float32 params from the seeded bfloat16 draw)."""
    arch = archs.find(cfg)
    mcfg = arch.program.model_config(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    harness.check_layout(shapes, cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make_params(shapes, SEED))
    return mcfg.replace(dtype="float32", **changes), params


def cal_tokens(vocab):
    return weights.calibration_tokens(SEED, vocab, 2, 32)


@pytest.fixture(scope="module")
def ref():
    cfg = tiny_cfg()
    with jax.default_matmul_precision("highest"):
        r = archs.find(cfg).reference.Reference(cfg, SEED, "float32")
        r.calibrate(cal_tokens(cfg["vocab_size"]))
    return r


PROMPTS = {0: 30, 1: 13, 2: 21, 3: 5, 4: 17}   # rid -> prompt length
NEW = 12


def serve(mcfg, params, slots=4, rids=tuple(PROMPTS)):
    """Serve the requests through the engine; returns each request's tokens
    and the logits each step computed for it, by position."""
    calib = harness.calibrate(params, cal_tokens(mcfg.vocab_size), mcfg)
    ecfg = EngineConfig(slots=slots, page_size=PAGE, chunk=CHUNK,
                        num_pages=slots * 16, max_pages_per_slot=16)
    eng = Engine(mcfg, params, ecfg, calib=calib)
    got: dict = {}
    real = eng._run_compiled

    def run(kind, fn, p, batch, caches, wins):
        occupied = eng._st.sched.occupied()
        if kind == "prefill":
            s = next(s for s in occupied if s.prefilling)
            rows = [(0, s.record.request.rid, s.pos + int(batch["valid"]) - 1)]
        else:
            rows = [(s.sid, s.record.request.rid, s.pos)
                    for s in occupied if not s.prefilling]
        logits, caches = real(kind, fn, p, batch, caches, wins)
        for row, rid, pos in rows:
            got[(rid, pos)] = np.asarray(logits[row, 0, :mcfg.vocab_size])
        return logits, caches

    eng._run_compiled = run
    rng = np.random.default_rng(7)
    prompts = {rid: tuple(int(t) for t in rng.integers(0, mcfg.vocab_size, n))
               for rid, n in PROMPTS.items()}
    rep = eng.run([Request(rid=rid, prompt=prompts[rid], max_new_tokens=NEW)
                   for rid in rids])
    assert eng.compiled_steps() == 2
    tokens = {r["rid"]: r["tokens"] for r in rep.requests}
    return prompts, tokens, got, eng


def mean_gap(ref, prompts, tokens, got) -> float:
    """Mean |program - reference| logit over every position served."""
    diffs = []
    for rid, prompt in prompts.items():
        seq = np.asarray(prompt + tuple(tokens[rid][:-1]), np.int32)
        with jax.default_matmul_precision("highest"):
            want = ref.served_logits([seq], [0], 64, len(seq),
                                     lambda j, lg: np.asarray(lg))[0]
        diffs += [np.abs(lg - want[pos]) for (r, pos), lg in got.items()
                  if r == rid]
    return float(np.mean(diffs))


def test_served_logits_match_the_reference(ref):
    """Prompts past the window (chunks crossing its edge), a ring that
    wraps in decode (16 positions a slot, 42 served), both pools."""
    mcfg, params = program(tiny_cfg())
    prompts, tokens, got, eng = serve(mcfg, params)
    assert eng.ring_pages == 4 and len(got) > 60
    assert mean_gap(ref, prompts, tokens, got) < TOL


def test_a_request_alone_equals_it_sharing_its_steps():
    """Dropless: a request's stream and logits do not depend on the
    requests that share its steps, nor on its slot (request 4 waits for a
    slot that another request leaves)."""
    mcfg, params = program(tiny_cfg())
    _, shared, got_shared, _ = serve(mcfg, params)
    for rid in (0, 4):
        _, alone, got_alone, _ = serve(mcfg, params, rids=(rid,))
        assert alone[rid] == shared[rid]
        for key, lg in got_alone.items():
            np.testing.assert_array_equal(lg, got_shared[key])


def test_routed_count_carries_past_the_device_sums_wrap():
    """The device's int32 sum of routed rows wraps; the host's total goes
    on counting."""
    import types
    st = types.SimpleNamespace(moe_rows_routed=2**31 - 3)
    Engine._read_routed(types.SimpleNamespace(_st=st),
                        {"moe_routed": jnp.int32(-2**31 + 4)})   # 7 rows on
    assert st.moe_rows_routed == 2**31 + 4


@pytest.mark.parametrize("fault", ["expert sites without codes", "no gate",
                                   "no qk-norm", "wider window", "rope on full"])
def test_a_missing_part_fails_the_tolerance(fault, ref, monkeypatch):
    mcfg, params = program(tiny_cfg())
    if fault == "expert sites without codes":
        mcfg = mcfg.replace(tdvmm_plan=mcfg.tdvmm_plan.with_rules(
            tdvmm_rule("moe.expert.*", enabled=False)))
    elif fault == "no gate":
        monkeypatch.setattr(attention, "_gated", lambda out, g: out)
    elif fault == "no qk-norm":
        mcfg = mcfg.replace(qk_norm=False)
    elif fault == "wider window":
        mcfg = mcfg.replace(swa_window=2 * WINDOW)
    else:
        mcfg = mcfg.replace(rope_full_layers=True)
    rids = (0, 2)
    prompts, tokens, got, _ = serve(mcfg, params, rids=rids)
    assert mean_gap(ref, {r: prompts[r] for r in rids}, tokens, got) > 2 * TOL


def moe_cfg(held=0, offset=0, td=False):
    """A small AFMoE expert layer: 16 experts, top-4, one shared expert."""
    from repro.configs import get_config, smoke
    cfg = smoke(get_config("trinity-mini"))
    m = dataclasses.replace(cfg.moe, n_experts=16, top_k=4, held=held,
                            held_offset=offset)
    return cfg.replace(moe=m, tdvmm_plan=TDVMMPlan(rules=(tdvmm_rule(
        "*", enabled=td, output_calibration=False),)))


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of 2 experts each, the shared expert counted
    once, give what the layer holding all 16 gives."""
    whole = moe_cfg()
    params = moe.init(jax.random.PRNGKey(0), whole, jnp.float32)
    params["router"]["b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, whole.d_model))
    want, _ = moe.apply(params, x, whole, dropless=True)
    shared = moe._expert_ffn(params["shared"], x.reshape(1, -1, whole.d_model),
                             whole, None, site_prefix="moe.shared").reshape(x.shape)
    total, routed = shared, 0
    for share in range(8):
        cfg = moe_cfg(held=2, offset=2 * share)
        p = dict(params, experts=jax.tree.map(
            lambda a: a[2 * share:2 * share + 2], params["experts"]))
        y, aux = moe.apply(p, x, cfg, dropless=True)
        total = total + (y - shared)
        routed += int(aux["moe_routed"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert routed == 2 * 24 * 4          # every row's top-4, each once


def test_routing_selects_by_score_plus_bias_and_weights_by_score():
    cfg = moe_cfg()
    m = cfg.moe
    params = moe.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    logits = np.asarray(x, np.float64) @ np.asarray(params["router"]["w"], np.float64)
    s = 1.0 / (1.0 + np.exp(-logits))
    # A bias that lifts one expert into every row's top-k where its score
    # alone would not put it.
    bias = np.zeros(16, np.float32)
    bias[5] = 2.0
    params["router"]["b"] = jnp.asarray(bias)
    ids, gates, _ = moe._route(params, x, cfg)
    ids, gates = np.asarray(ids), np.asarray(gates)
    want = np.argsort(-(s + bias), -1, kind="stable")[:, :m.top_k]
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want, -1))
    assert (ids == 5).any(-1).all()
    assert not (np.argsort(-s, -1)[:, :m.top_k] == 5).any(-1).all()
    chosen = np.take_along_axis(s, ids, -1)
    np.testing.assert_allclose(
        gates, chosen / chosen.sum(-1, keepdims=True) * m.route_scale, rtol=1e-5)
    assert abs(m.route_scale - 2.826) < 1e-12


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_step_programs_name_the_expert_layers_phases(kind):
    """Both compiled steps carry the ``MOE_SCOPES`` names (and every
    ``STEP_SCOPES`` name) as HLO op_name metadata, for the profile reduction."""
    import re

    from repro.runtime.trace import MOE_SCOPES, STEP_SCOPES
    mcfg, params = program(tiny_cfg())
    calib = harness.calibrate(params, cal_tokens(mcfg.vocab_size), mcfg)
    ecfg = EngineConfig(slots=2, page_size=PAGE, chunk=CHUNK, num_pages=16,
                        max_pages_per_slot=8)
    eng = Engine(mcfg, params, ecfg, calib=calib)
    eng.start([])
    sd, i32 = jax.ShapeDtypeStruct, np.int32
    p, r = ecfg.resolved_max_pages, eng.ring_pages
    batch = ({"inputs": sd((1, CHUNK), i32), "block_row": sd((p,), i32),
              "offset": sd((), i32), "valid": sd((), i32), "ring_row": sd((r,), i32)}
             if kind == "prefill" else
             {"inputs": sd((2, 1), i32), "block_tables": sd((2, p), i32),
              "pos": sd((2,), i32), "active": sd((2,), np.bool_),
              "ring_tables": sd((2, r), i32)})
    fn = eng._prefill if kind == "prefill" else eng._decode
    text = fn.lower(eng.params, batch, eng._st.caches,
                    eng._windows).compile().as_text()
    parts = {part for path in re.findall(r'op_name="([^"]*)"', text)
             for part in path.split("/")}
    assert set(STEP_SCOPES + MOE_SCOPES) <= parts, set(STEP_SCOPES + MOE_SCOPES) - parts
