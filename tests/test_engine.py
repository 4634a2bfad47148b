"""Continuous-batching serving engine: paged KV cache, slot scheduler,
two-compiled-step invariant, evict-before-poison, energy accounting.

The load-bearing contract (acceptance criterion, jnp backend): per-request
token streams from the batched paged engine are bit-identical to running
each request alone at the same calibrated windows — slots never couple.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TDVMMPlan, get_config, smoke, tdvmm_rule
from repro.core import energy
from repro.models import attention, model
from repro.runtime.engine import (Engine, EngineConfig, Request,
                                  static_baseline)
from repro.runtime.paged_cache import PagePool, pages_for


def _cfg():
    return smoke(get_config("qwen1.5-0.5b")).replace(tdvmm_plan=TDVMMPlan(
        rules=(tdvmm_rule("ffn.*", enabled=True, backend="jnp"),)))


@pytest.fixture(scope="module")
def served():
    """Shared (cfg, params, calib): one calibration pass for the module."""
    cfg = _cfg()
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"inputs": jax.random.randint(
        jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)}
    calib = model.calibrate(params, batch, cfg, max_len=48)
    return cfg, params, calib


def _trace(vocab, n=4, seed=0, prompt=(3, 11), gen=(2, 6), max_gap=0):
    rng = np.random.default_rng(seed)
    reqs, arrival = [], 0
    for rid in range(n):
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(t) for t in rng.integers(
                0, vocab, rng.integers(*prompt))),
            max_new_tokens=int(rng.integers(*gen)),
            arrival_step=arrival))
        arrival += int(rng.integers(0, max_gap + 1))
    return reqs


def _solo_dense_greedy(cfg, params, calib, req):
    """Reference: the request alone through the dense-cache
    prefill_step/decode_step path at the same calibrated windows."""
    caches = model.init_caches(cfg, 1, len(req.prompt) + req.max_new_tokens)
    logits, caches = model.prefill_step(
        params, {"inputs": jnp.asarray([req.prompt], jnp.int32)}, caches,
        cfg, calib=calib)
    toks = [int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))]
    while len(toks) < req.max_new_tokens:
        logits, caches = model.decode_step(
            params, {"inputs": jnp.asarray([[toks[-1]]], jnp.int32)}, caches,
            cfg, calib=calib)
        toks.append(int(jnp.argmax(logits[0, -1, :cfg.vocab_size])))
    return toks


# --------------------------------------------------------------------------
# Acceptance: batched engine == each request alone (dense path), jnp backend
# --------------------------------------------------------------------------
def test_engine_bit_identical_to_solo_dense(served):
    cfg, params, calib = served
    reqs = _trace(cfg.vocab_size, n=4)
    # chunk covers every prompt: the whole prompt is one prefill chunk, the
    # exact computation prefill_step runs (masked page tail == exact zeros).
    eng = Engine(cfg, params,
                 EngineConfig(slots=3, page_size=4, num_pages=32, chunk=16),
                 calib=calib)
    rep = eng.run(reqs)
    assert rep.compiled_steps == 2
    assert rep.nan_logit_steps == 0
    for req, rec in zip(reqs, rep.requests):
        assert rec["finish_reason"] == "max_tokens"
        assert rec["tokens"] == _solo_dense_greedy(cfg, params, calib, req), \
            f"slot coupling: request {req.rid} diverged from its solo run"


def test_engine_chunked_prefill_matches_solo_engine(served):
    """Chunked prefill (chunk < prompt) stays request-isolated: batched run
    == B=1 run with the same chunking."""
    cfg, params, calib = served
    reqs = _trace(cfg.vocab_size, n=4, seed=3, prompt=(6, 14))
    ecfg = EngineConfig(slots=3, page_size=4, num_pages=32, chunk=4)
    rep = Engine(cfg, params, ecfg, calib=calib).run(reqs)
    solo_cfg = EngineConfig(slots=1, page_size=4, num_pages=32, chunk=4)
    for req, rec in zip(reqs, rep.requests):
        solo = Engine(cfg, params, solo_cfg, calib=calib).run(
            [Request(req.rid, req.prompt, req.max_new_tokens, 0)])
        assert rec["tokens"] == solo.requests[0]["tokens"]


def test_engine_requires_pinned_windows(served):
    cfg, params, _ = served
    with pytest.raises(ValueError, match="pinned readout window"):
        Engine(cfg, params, EngineConfig())


# --------------------------------------------------------------------------
# Satellite: evict-before-poison (page budget hit => clean "evicted" finish)
# --------------------------------------------------------------------------
def test_eviction_finishes_cleanly_without_poisoning_neighbors(served):
    cfg, params, calib = served
    # rid 0 wants far more tokens than its page budget; rid 1/2 are small.
    reqs = [Request(0, tuple(range(1, 9)), max_new_tokens=40),
            Request(1, tuple(range(9, 14)), max_new_tokens=4),
            Request(2, tuple(range(14, 20)), max_new_tokens=5)]
    ecfg = EngineConfig(slots=3, page_size=4, num_pages=16,
                        max_pages_per_slot=3, chunk=16)
    rep = Engine(cfg, params, ecfg, calib=calib).run(reqs)
    by_rid = {r["rid"]: r for r in rep.requests}
    # budget = 3 pages * 4 = 12 positions, prompt 8 -> 4 decode writes; the
    # token sampled after the last write needs no page, so 5 tokens stream.
    assert by_rid[0]["finish_reason"] == "evicted"
    assert len(by_rid[0]["tokens"]) == 5
    # the would-be NaN-poisoning write never happened: no NaN logit row was
    # observed on ANY active slot in the whole run,
    assert rep.nan_logit_steps == 0
    # and the neighbors' streams are exactly their solo runs.
    for rid in (1, 2):
        assert by_rid[rid]["finish_reason"] == "max_tokens"
        assert by_rid[rid]["tokens"] == _solo_dense_greedy(
            cfg, params, calib, reqs[rid])
    # the evicted prefix itself is still correct (truncated solo stream)
    solo0 = _solo_dense_greedy(cfg, params, calib, reqs[0].__class__(
        0, reqs[0].prompt, 5))
    assert by_rid[0]["tokens"] == solo0


def test_oversized_prompt_rejected_as_evicted(served):
    cfg, params, calib = served
    reqs = [Request(0, tuple(range(1, 30)), max_new_tokens=4),
            Request(1, tuple(range(1, 6)), max_new_tokens=3)]
    ecfg = EngineConfig(slots=2, page_size=4, num_pages=16,
                        max_pages_per_slot=4, chunk=8)
    rep = Engine(cfg, params, ecfg, calib=calib).run(reqs)
    assert rep.requests[0]["finish_reason"] == "evicted"
    assert rep.requests[0]["tokens"] == []
    assert rep.requests[1]["finish_reason"] == "max_tokens"
    assert rep.requests[1]["tokens"] == _solo_dense_greedy(
        cfg, params, calib, reqs[1])


# --------------------------------------------------------------------------
# Satellite: int8 KV quantization under page reuse (write -> free -> realloc)
# --------------------------------------------------------------------------
@contextlib.contextmanager
def _kv_int8(on):
    attention.set_kv_cache_int8(on)
    try:
        yield
    finally:
        attention.set_kv_cache_int8(False)


def _assert_isolated(served, reqs, ecfg, int8, dense_ref):
    """Serve ``reqs`` on ``ecfg`` with int8 KV on or off: every request
    finishes on max_tokens with its reference's tokens — its dense solo run
    (``dense_ref``) or its run alone on a one-slot engine of 8 pages."""
    cfg, params, calib = served
    with _kv_int8(int8):
        rep = Engine(cfg, params, ecfg, calib=calib).run(reqs)
        solo_cfg = EngineConfig(slots=1, page_size=ecfg.page_size,
                                num_pages=8, chunk=ecfg.chunk)
        for req, rec in zip(reqs, rep.requests):
            assert rec["finish_reason"] == "max_tokens"
            want = (_solo_dense_greedy(cfg, params, calib, req) if dense_ref
                    else Engine(cfg, params, solo_cfg, calib=calib).run(
                        [Request(req.rid, req.prompt, req.max_new_tokens, 0)]
                    ).requests[0]["tokens"])
            assert rec["tokens"] == want, f"int8={int8}: request {req.rid}"
        assert rep.nan_logit_steps == 0


@pytest.mark.parametrize("int8", [False, True])
def test_page_reuse_no_stale_scale_bleed(served, int8):
    """A new request reallocating a finished request's pages must see no
    trace of the old codes/scales (stale positions are masked to exact
    zeros; every written position carries its own fresh scale)."""
    # pool = exactly one request's worth of pages: B MUST reuse A's pages.
    reqs = [Request(0, tuple(range(1, 11)), max_new_tokens=5,
                    arrival_step=0),
            Request(1, tuple(range(40, 49)), max_new_tokens=5,
                    arrival_step=1)]
    ecfg = EngineConfig(slots=2, page_size=4, num_pages=4, chunk=8)
    assert pages_for(15, 4) == 4          # A fills the whole pool
    _assert_isolated(served, reqs, ecfg, int8, dense_ref=False)


@pytest.mark.parametrize("int8", [False, True])
def test_last_chunk_past_block_row(served, int8):
    """The last prefill chunk may reach past the slot's block row
    (offset + C > P * page_size): its rows are merged into the read by
    clipped position, so the real ones land where they belong and the
    padded ones are never read.  bf16 KV: each request equals its dense
    solo run; int8 KV: its solo run on an engine whose rows are long."""
    # 3 pages of 4 hold 12 positions; a 10-token prompt in chunks of 8
    # ends with the chunk [8, 16).
    reqs = [Request(0, tuple(range(1, 11)), max_new_tokens=3),
            Request(1, tuple(range(20, 31)), max_new_tokens=2)]
    ecfg = EngineConfig(slots=2, page_size=4, num_pages=8,
                        max_pages_per_slot=3, chunk=8)
    assert 8 + ecfg.chunk > ecfg.max_pages_per_slot * ecfg.page_size
    _assert_isolated(served, reqs, ecfg, int8, dense_ref=not int8)


def _eqns(jaxpr, top=True):
    """(equation, whether it is at the top level) for every equation of a
    jaxpr and of the jaxprs nested in its equations."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, top
        stack = list(eqn.params.values())
        while stack:
            v = stack.pop()
            if isinstance(v, ClosedJaxpr):
                v = v.jaxpr
            if isinstance(v, Jaxpr):
                yield from _eqns(v, False)
            elif isinstance(v, (tuple, list)):
                stack.extend(v)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_step_programs_keep_pools_out_of_layer_scan(served, step, int8):
    """The page pools are no scan carry, input slice or output: each step
    program's only pool-shaped values are its inputs and ONE scatter per
    pool after the layer loop, which writes every layer's new rows."""
    cfg, params, calib = served
    ecfg = EngineConfig(slots=3, page_size=4, num_pages=16, chunk=8)
    n_rows = ecfg.resolved_max_pages
    if step == "decode":
        fn = model.decode_slots
        batch = {"inputs": jnp.zeros((3, 1), jnp.int32),
                 "block_tables": jnp.zeros((3, n_rows), jnp.int32),
                 "pos": jnp.zeros((3,), jnp.int32),
                 "active": jnp.ones((3,), bool)}
    else:
        fn = model.prefill_chunk
        batch = {"inputs": jnp.zeros((1, ecfg.chunk), jnp.int32),
                 "block_row": jnp.zeros((n_rows,), jnp.int32),
                 "offset": jnp.int32(0), "valid": jnp.int32(5)}
    with _kv_int8(int8):
        caches = model.init_paged_caches(cfg, ecfg.num_pages, ecfg.page_size)
        closed = jax.make_jaxpr(
            lambda p, b, c: fn(p, b, c, cfg, calib=calib))(params, batch,
                                                           caches)
    stacked = {tuple(leaf.shape) for leaf in jax.tree.leaves(caches)}
    pool_shapes = stacked | {shape[1:] for shape in stacked}
    eqns = list(_eqns(closed.jaxpr))
    loops = [e for e, _ in eqns if e.primitive.name in ("scan", "while")]
    assert loops
    for e in loops:
        assert not [v.aval.shape for v in e.outvars
                    if tuple(v.aval.shape) in pool_shapes]
    writers = [(e, top) for e, top in eqns
               if any(tuple(v.aval.shape) in pool_shapes for v in e.outvars)]
    assert [(e.primitive.name, top) for e, top in writers] \
        == [("scatter", True)] * len(jax.tree.leaves(caches))
    pools_out = closed.jaxpr.outvars[-len(writers):]
    assert [e.outvars[0] for e, _ in writers] == pools_out


# --------------------------------------------------------------------------
# Satellite: scheduler determinism across slot assignment order
# --------------------------------------------------------------------------
def test_slot_assignment_order_does_not_change_streams(served):
    cfg, params, calib = served
    reqs = _trace(cfg.vocab_size, n=6, seed=7, prompt=(3, 12), gen=(2, 7),
                  max_gap=2)
    kw = dict(page_size=4, num_pages=32, chunk=8)
    rep_f = Engine(cfg, params, EngineConfig(slots=3, slot_order="fifo", **kw),
                   calib=calib).run(reqs)
    rep_l = Engine(cfg, params, EngineConfig(slots=3, slot_order="lifo", **kw),
                   calib=calib).run(reqs)
    for a, b in zip(rep_f.requests, rep_l.requests):
        assert a["tokens"] == b["tokens"]
        assert a["finish_reason"] == b["finish_reason"]
        assert a["finished_step"] == b["finished_step"]
    assert rep_f.steps == rep_l.steps


# --------------------------------------------------------------------------
# Acceptance: engine beats the static batch on the ragged trace (steps,
# KV memory high-water, utilization)
# --------------------------------------------------------------------------
def test_engine_beats_static_batch_on_ragged_trace(served):
    cfg, params, calib = served
    reqs = _trace(cfg.vocab_size, n=10, seed=0, prompt=(4, 14), gen=(2, 25),
                  max_gap=1)
    ecfg = EngineConfig(slots=4, page_size=4, num_pages=64, chunk=8)
    rep = Engine(cfg, params, ecfg, calib=calib).run(reqs)
    static = static_baseline(reqs, ecfg.slots, ecfg.chunk)
    assert rep.steps < static["wall_steps"]
    assert rep.utilization > static["utilization"]
    max_len = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    dense = jax.eval_shape(lambda: model.init_caches(cfg, ecfg.slots, max_len))
    dense_bytes = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(dense))
    assert rep.kv_high_water_bytes < dense_bytes
    assert rep.nan_logit_steps == 0
    assert rep.compiled_steps == 2


# --------------------------------------------------------------------------
# Energy accounting
# --------------------------------------------------------------------------
def test_serving_energy_model_chain_saves_io(served):
    cfg, _, _ = served
    unchained = energy.serving_energy_model(cfg, tile_n=64)
    chained_cfg = cfg.replace(tdvmm_plan=cfg.tdvmm_plan.with_rules(
        tdvmm_rule("ffn.in", chain=True)))
    chained = energy.serving_energy_model(chained_cfg, tile_n=64)
    assert unchained["ops_per_token"] > 0
    # chaining drops one readout + one DAC: same ops, strictly less energy
    assert chained["ops_per_token"] == unchained["ops_per_token"]
    assert chained["energy_per_token_j"] < unchained["energy_per_token_j"]
    assert chained["per_site"]["ffn.in"]["io_factor"] == 0.5
    assert chained["per_site"]["ffn.out"]["io_factor"] == 0.5
    # disabled sites don't meter
    off = energy.serving_energy_model(smoke(get_config("qwen1.5-0.5b")))
    assert off["ops_per_token"] == 0


def test_serving_energy_model_chain_saves_io_moe_sites(served):
    """Chain-aware I/O halving on the MoE chainable pairs: both
    ``moe.expert.in -> .out`` and ``moe.shared.in -> .out`` drop the
    intermediate p-bit boundary (io_factor 0.5 on each end), with ops
    unchanged and strictly less energy per token."""
    # kimi-k2 smoke: the only arch with shared experts (n_shared_experts=1)
    base = smoke(get_config("kimi-k2-1t-a32b"))
    assert base.moe.n_shared_experts >= 1
    on = base.replace(tdvmm_plan=TDVMMPlan(
        rules=(tdvmm_rule("moe.*", enabled=True, backend="jnp"),)))
    unchained = energy.serving_energy_model(on, tile_n=64)
    chained_cfg = on.replace(tdvmm_plan=on.tdvmm_plan.with_rules(
        tdvmm_rule("moe.expert.in", chain=True),
        tdvmm_rule("moe.shared.in", chain=True)))
    chained = energy.serving_energy_model(chained_cfg, tile_n=64)
    assert unchained["ops_per_token"] > 0
    assert chained["ops_per_token"] == unchained["ops_per_token"]
    assert chained["energy_per_token_j"] < unchained["energy_per_token_j"]
    for site in ("moe.expert.in", "moe.expert.out",
                 "moe.shared.in", "moe.shared.out"):
        assert unchained["per_site"][site]["io_factor"] == 1.0, site
        assert chained["per_site"][site]["io_factor"] == 0.5, site
    # each chained pair saves exactly half its I/O energy; the expert pair
    # (top_k matrices) saves more joules than the single shared pair
    def pair_saving(up, down):
        return sum(unchained["per_site"][s]["energy_per_token_j"]
                   - chained["per_site"][s]["energy_per_token_j"]
                   for s in (up, down))
    assert pair_saving("moe.expert.in", "moe.expert.out") > \
        pair_saving("moe.shared.in", "moe.shared.out") > 0
    # chaining only the expert pair leaves the shared boundary digital
    expert_only = energy.serving_energy_model(on.replace(
        tdvmm_plan=on.tdvmm_plan.with_rules(
            tdvmm_rule("moe.expert.in", chain=True))), tile_n=64)
    assert expert_only["per_site"]["moe.shared.in"]["io_factor"] == 1.0
    assert expert_only["per_site"]["moe.expert.in"]["io_factor"] == 0.5


def test_token_cost_and_request_energy_bounds(served):
    cfg, _, _ = served
    table = energy.serving_energy_model(cfg, tile_n=64)
    ops1, e1 = energy.token_cost(table)
    assert (ops1, e1) == (table["ops_per_token"],
                          table["energy_per_token_j"])
    ops5, e5 = energy.token_cost(table, 5)
    assert ops5 == pytest.approx(5 * ops1) and e5 == pytest.approx(5 * e1)
    b = energy.request_energy_bounds(table, prompt_len=7, max_new_tokens=4)
    # min = prompt + 1 token (the cheapest *served* outcome), full = budget
    assert b["min_tokens"] == 8 and b["full_tokens"] == 11
    assert b["min_energy_j"] == pytest.approx(8 * e1)
    assert b["full_energy_j"] == pytest.approx(11 * e1)
    assert b["min_ops"] == pytest.approx(8 * ops1)
    assert b["min_energy_j"] < b["full_energy_j"]
    with pytest.raises(ValueError, match=">= 1"):
        energy.request_energy_bounds(table, 0, 4)
    with pytest.raises(ValueError, match=">= 1"):
        energy.request_energy_bounds(table, 7, 0)


def test_engine_per_request_energy_accounting(served):
    cfg, params, calib = served
    reqs = [Request(0, tuple(range(1, 7)), max_new_tokens=3)]
    ecfg = EngineConfig(slots=1, page_size=4, num_pages=8, chunk=8, tile_n=64)
    eng = Engine(cfg, params, ecfg, calib=calib)
    rep = eng.run(reqs)
    tokens = len(reqs[0].prompt) + 3
    assert rep.requests[0]["analog_ops"] == pytest.approx(
        tokens * eng.energy["ops_per_token"])
    assert rep.requests[0]["analog_energy_j"] == pytest.approx(
        tokens * eng.energy["energy_per_token_j"])
    assert rep.fj_per_op == pytest.approx(eng.energy["fj_per_op"])
    assert rep.tokens_per_joule > 0


# --------------------------------------------------------------------------
# Page pool mechanics
# --------------------------------------------------------------------------
def test_page_pool_deterministic_alloc_free():
    pool = PagePool(num_pages=6, page_size=4)
    a = pool.alloc(3)
    assert a == [0, 1, 2] and pool.in_use == 3
    b = pool.alloc(2)
    assert b == [3, 4]
    assert pool.alloc(2) is None and pool.in_use == 5   # nothing taken
    pool.free(a)
    assert pool.alloc(4) == [0, 1, 2, 5]
    assert pool.high_water == 6
    with pytest.raises(ValueError):
        pool.free([3, 3])
    assert pool.trash_page == 6


def test_page_pool_dp_ranks_partition_and_snapshot():
    """ranks=dp partitions the pool: rank r owns ids offset by r*(P+1),
    rank 0 is bit-identical to the ranks=1 pool, and only the single global
    trash page exists (last device row)."""
    pool = PagePool(num_pages=6, page_size=4, ranks=2)
    assert pool.total_pages == 12 and pool.trash_page == 13
    # rank 0 mirrors the single-rank layout exactly
    assert pool.alloc(3, rank=0) == [0, 1, 2]
    # rank 1's region starts beyond rank 0's trash row (id 6)
    b = pool.alloc(2, rank=1)
    assert b == [7, 8] and pool.in_use == 5
    # per-rank exhaustion: rank 1 has 4 pages left, rank 0 has 3
    assert pool.alloc(4, rank=0) is None
    assert pool.alloc(4, rank=1) == [9, 10, 11, 12]
    # free() infers the rank from the id; cross-region ids are rejected
    pool.free(b)
    assert pool.alloc(2, rank=1) == [7, 8]
    with pytest.raises(ValueError, match="out-of-range"):
        pool.free([6])                      # rank 0's trash row: not a page
    with pytest.raises(ValueError):
        pool.alloc(1, rank=2)
    # free-list snapshot round-trips (what Engine.snapshot carries)
    lists = pool.free_lists()
    pool2 = PagePool(num_pages=6, page_size=4, ranks=2)
    pool2.restore_free(lists)
    assert pool2.free_lists() == lists
    assert pool2.in_use == pool.in_use
    with pytest.raises(ValueError, match="rank free-lists"):
        PagePool(num_pages=6, page_size=4).restore_free(lists)


# --------------------------------------------------------------------------
# Acceptance (PR 9): mesh-sharded engine, (1,1) mesh == no mesh exactly
# --------------------------------------------------------------------------
def test_mesh_1x1_engine_bit_identical_to_meshless(served):
    """The sharded engine on a trivial (1,1) mesh replays the fixed-seed
    ragged trace bit-identically to the meshless engine — streams, finish
    reasons, finish steps — with compiled_steps == 2 through the sharded
    path (shard_map over size-1 axes, device_put'ed params/pools/batches)."""
    from repro.launch.mesh import make_test_mesh

    cfg, params, calib = served
    reqs = _trace(cfg.vocab_size, n=6, seed=5, prompt=(3, 12), gen=(2, 7),
                  max_gap=1)
    ecfg = EngineConfig(slots=3, page_size=4, num_pages=32, chunk=4)
    base = Engine(cfg, params, ecfg, calib=calib).run(reqs)
    meshed_eng = Engine(cfg, params, ecfg, calib=calib,
                        mesh=make_test_mesh(1, 1))
    meshed = meshed_eng.run(reqs)
    assert meshed.compiled_steps == 2
    assert meshed.devices == 1 and meshed.total_slots == ecfg.slots
    assert meshed.steps == base.steps
    assert meshed.page_high_water == base.page_high_water
    for a, b in zip(base.requests, meshed.requests):
        assert a["tokens"] == b["tokens"], (a, b)
        assert a["finish_reason"] == b["finish_reason"]
        assert a["finished_step"] == b["finished_step"]
    # snapshot layout is the meshless v3 layout (dp=1, one free list)
    snap = meshed_eng.snapshot()
    import json as _json
    meta = _json.loads(np.asarray(snap["meta"], np.uint8).tobytes())
    assert meta["dp"] == 1 and len(meta["pool"]["free"]) == 1
