"""The AFMoE package (``bench/archs/afmoe``): the configuration file keeps
Trinity-Mini's published widths and the program's parameter tree is the
reference's layout, the launch list is the launches a step makes, and the
model's work counts window layers' keys up to the window."""
from __future__ import annotations

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchcells import ROOT  # noqa: F401
from bench import archs, harness, work  # noqa: E402

V5E = work.peaks("TPU v5 lite")


def trinity() -> dict:
    return json.loads((ROOT / "bench/configs/trinity-mini-1chip.json").read_text())


def tiny() -> dict:
    cfg = trinity()
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=8, vocab_size=500, num_experts=4,
               num_experts_per_tok=2, sliding_window=8)
    cfg["published"] = {"num_hidden_layers": 32, "num_experts": 8}
    return cfg


def test_the_file_is_one_chips_share_at_published_widths():
    cfg = trinity()
    assert archs.find(cfg).name == "afmoe"
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"], cfg["vocab_size"]) == (
        2048, 32, 4, 128, 6144, 1024, 8, 2048, 200192)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts"]
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["expert_offset"]) == (8, 16, 0)
    kinds = cfg["layer_types"]
    assert len(kinds) == 32 and kinds[:8] == (["sliding_attention"] * 3
                                              + ["full_attention"]) * 2
    mcfg = archs.find(cfg).program.model_config(cfg)
    assert (mcfg.moe.n_experts, mcfg.moe.resolved_held, mcfg.moe.first_k_dense) == (128, 16, 2)
    assert (mcfg.moe.score_func, mcfg.moe.route_scale, mcfg.moe.selection_bias) == (
        "sigmoid", 2.826, True)
    shapes = jax.eval_shape(lambda: __import__("repro.models.model", fromlist=["x"])
                            .init_params(jax.random.PRNGKey(0), mcfg))
    harness.check_layout(shapes, cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 1.7e9 < n < 1.8e9              # ~1.76 B parameters, 3.5 GB in bf16


def _pallas_launches(jaxpr, times=1, out=None):
    """Leading dims (G) of every pallas_call's first operand in a jaxpr,
    each as often as the scans around it run it."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out += [eqn.invars[0].aval.shape[0]] * times
        n = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_launches(inner, times * n, out)
    return out


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_step_launches_are_the_launches_a_step_makes(step):
    from repro.configs import plan
    from repro.models import model
    from repro.runtime.paged_cache import ring_pages
    cfg = tiny()
    cfg["tdvmm"] = dict(cfg["tdvmm"], backend="pallas")
    mcfg = archs.find(cfg).program.model_config(cfg)
    slots, chunk, ps, pages = 6, 16, 8, 4
    ring = ring_pages(mcfg.swa_window, chunk, ps)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    caches = jax.eval_shape(lambda: model.init_paged_caches(
        mcfg, slots * pages, ps, ring_pages=slots * ring))
    # The calibrated windows' shapes: one per member of the grouped qkv
    # launch, one per held expert, one for the shared expert's tile.
    tiles = {"attn.qkv": (4,), "moe.expert.in": (4,), "moe.expert.out": (4,),
             "moe.shared.in": (1,), "moe.shared.out": (1,)}
    wins = {site: jnp.ones(tiles.get(site, ()), jnp.float32)
            for site in plan.model_sites(mcfg)}
    i32 = jnp.int32
    if step == "decode":
        batch = {"inputs": jnp.zeros((slots, 1), i32),
                 "block_tables": jnp.zeros((slots, pages), i32),
                 "pos": jnp.zeros((slots,), i32), "active": jnp.ones((slots,), bool),
                 "ring_tables": jnp.zeros((slots, ring), i32)}
        fn, rows, head = model.decode_slots, slots, slots
    else:
        batch = {"inputs": jnp.zeros((1, chunk), i32), "block_row": jnp.zeros((pages,), i32),
                 "offset": i32(0), "valid": i32(chunk), "ring_row": jnp.zeros((ring,), i32)}
        fn, rows, head = model.prefill_chunk, chunk, 1
    jaxpr = jax.make_jaxpr(lambda p, b, c, w: fn(p, b, c, mcfg, windows=w))(
        params, batch, caches, wins)
    got = sorted(_pallas_launches(jaxpr.jaxpr))
    want = sorted(g for _, g, m, _, _, c in archs.find(cfg).work.step_launches(cfg, rows, head)
                  for _ in range(c))
    assert got == want
    assert got.count(4) == 3 * 6          # the held experts: 3 launches x 6 MoE layers


def test_model_work_counts_window_keys_up_to_the_window():
    cfg = tiny()
    w, ranges = 8, [(0, 5), (5, 12), (20, 23)]
    full = sum(p + 1 for b, e in ranges for p in range(b, e))
    window = sum(min(p + 1, w) for b, e in ranges for p in range(b, e))
    assert (full, window) == (15 + 63 + 66, 15 + (6 + 7 + 5 * 8) + 3 * 8)
    int8, bf16 = archs.find(cfg).work.model_work(cfg, 26, 3, ranges)
    assert bf16 == 4 * 4 * 16 * (2 * full + 6 * window)
    one = copy.deepcopy(cfg)
    one["num_experts"] = 8               # every expert held: top-2 a token
    i_all, _ = archs.find(one).work.model_work(one, 26, 3, ranges)
    assert i_all - int8 == pytest.approx(26 * 6 * 6 * 64 * 32 * (2 - 1))


def test_launch_costs_price_what_the_kernel_computes():
    cfg = trinity()
    launches = {s: (g, m, k, n, c) for s, g, m, k, n, c in
                archs.find(cfg).work.step_launches(cfg, 96, 96)}
    assert launches["moe.expert.in"] == (16, 96, 2048, 1024, 12)
    assert launches["moe.expert.out"] == (16, 96, 1024, 2048, 6)
    assert launches["attn.qkv"] == (1, 96, 2048, 2 * 4096 + 2 * 512, 8)
    assert work.kernel_launches(cfg, 1, 96, 1, 512) == 2 * (8 + 8 + 4 + 2 + 12 + 6 + 12 + 6 + 1)


@pytest.mark.parametrize("counts, want", [
    ({"moe_rows_routed": 96, "moe_rows_computed": 1536}, 6.25),
    ({"moe_rows_routed": 0, "moe_rows_computed": 0}, None),
    ({"kv_pages_read": 400, "kv_pages_live": 84}, None),
])
def test_moe_row_share_reader(counts, want):
    """Routed over computed rows; nothing where the program has no expert
    layer or no such counters (a dense model, or a program before them)."""
    from bench import metrics
    got = metrics.read("moe_row_share", {"counts": counts})
    assert got == (None if want is None else pytest.approx(want))
