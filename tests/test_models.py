"""Model-component unit tests: attention (incl. SWA + flash), MoE dispatch,
SSD chunking, rope, and the TD-VMM layer inside blocks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, smoke
from repro.configs.base import TDVMMPlan, tdvmm_rule
from repro.models import attention, common, moe, ssm
from repro.models.ssm import ssd_chunked
from repro.kernels.ssd.ref import ssd_naive


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def _attn_cfg(**kw):
    cfg = smoke(get_config("yi-34b"))
    return cfg.replace(**kw) if kw else cfg


@pytest.mark.parametrize("window", [None, 1024, 1536])
def test_flash_matches_dense(window):
    """The blocked online-softmax path must equal the direct softmax path,
    causal and under a sliding window (incl. windows not aligned to the
    block size)."""
    cfg = _attn_cfg(swa_window=window)
    b, s, h, d = 2, 4096, cfg.n_heads, cfg.resolved_head_dim
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (b, s, h, d)) * 0.5
    kk = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.n_kv_heads, d)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.n_kv_heads, d))
    out_flash = attention._attend_flash(q, kk, v, cfg)
    mask = attention._causal_mask(s, s, 0, window)
    out_dense = attention._attend(q, kk, v, mask, cfg)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_dense),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("s", [2049, 3000])
def test_flash_non_block_multiple_s(s):
    """Bugfix: S > FLASH_THRESHOLD not divisible by the flash block used to
    hit a trace-time assert; the padded+masked path must match dense."""
    cfg = _attn_cfg()
    b, h, d = 1, cfg.n_heads, cfg.resolved_head_dim
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d)) * 0.5
    kk = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.n_kv_heads, d)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.n_kv_heads, d))
    mask = attention._causal_mask(s, s, 0, None)
    out_dense = attention._attend(q, kk, v, mask, cfg)
    out_flash = attention._attend_flash(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_dense),
                               rtol=2e-3, atol=2e-3)


def test_flash_non_block_multiple_s_swa():
    """Same ragged-length fix under a sliding window (padded key tail must
    stay masked when the window mask is also active)."""
    s = 2049
    cfg = _attn_cfg(swa_window=1000)
    b, h, d = 1, cfg.n_heads, cfg.resolved_head_dim
    q = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, d)) * 0.5
    kk = jax.random.normal(jax.random.PRNGKey(4), (b, s, cfg.n_kv_heads, d)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(5), (b, s, cfg.n_kv_heads, d))
    mask = attention._causal_mask(s, s, 0, cfg.swa_window)
    out_dense = attention._attend(q, kk, v, mask, cfg)
    out_flash = attention._attend_flash(q, kk, v, cfg)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_dense),
                               rtol=2e-3, atol=2e-3)


def test_apply_train_odd_length_above_flash_threshold():
    """End-to-end: apply_train at S=2049 routes through flash without the
    old trace-time block-divisibility assert."""
    cfg = _attn_cfg()
    params = attention.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s = 1, attention.FLASH_THRESHOLD + 1
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.3
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    y = attention.apply_train(params, x, cfg, positions)
    assert y.shape == (b, s, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(y)))


def test_decode_past_cache_capacity_rejected():
    """Bugfix: non-SWA decode past max_len used to silently overwrite the
    last KV slot; with concrete positions it must raise."""
    cfg = _attn_cfg()
    params = attention.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s = 1, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.3
    cache = attention.init_cache(cfg, b, max_len=s, dtype=jnp.float32)
    _, cache = attention.apply_prefill(params, x, cfg, cache)
    tok = jax.random.normal(jax.random.PRNGKey(2), (b, 1, cfg.d_model)) * 0.3
    assert int(cache.pos[0]) == s      # cache exactly full
    with pytest.raises(ValueError, match="capacity"):
        attention.apply_decode(params, tok, cfg, cache)


def test_decode_past_cache_capacity_jit_poisons_not_corrupts():
    """Under jit (traced positions) an overflowing row fails loudly — NaN
    output, frozen pos — and leaves the cache bytes untouched."""
    cfg = _attn_cfg()
    params = attention.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s = 2, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.3
    cache = attention.init_cache(cfg, b, max_len=s + 1, dtype=jnp.float32)
    _, cache = attention.apply_prefill(params, x, cfg, cache)
    # row 0 overflows (pos == size), row 1 still has one free slot
    cache = cache._replace(pos=jnp.array([s + 1, s], jnp.int32))
    tok = jax.random.normal(jax.random.PRNGKey(2), (b, 1, cfg.d_model)) * 0.3
    step = jax.jit(lambda p, t, c: attention.apply_decode(p, t, cfg, c))
    y, new_cache = step(params, tok, cache)
    assert bool(jnp.all(jnp.isnan(y[0]))) and bool(jnp.all(jnp.isfinite(y[1])))
    np.testing.assert_array_equal(np.asarray(new_cache.k[0]),
                                  np.asarray(cache.k[0]))
    assert int(new_cache.pos[0]) == s + 1 and int(new_cache.pos[1]) == s + 1


# --------------------------------------------------------------------------
# grouped-projection TD-VMM launches
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_grouped_qkv_matches_sequential_dense(backend):
    """attn.qkv as ONE grouped launch == the three per-projection td_matmul
    calls, bit for bit (matching data-calibrated windows)."""
    cfg = _attn_cfg().replace(tdvmm_plan=TDVMMPlan(rules=(
        tdvmm_rule("attn.qkv", enabled=True, backend=backend),)))
    params = attention.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model)) * 0.3
    td = cfg.site_tdvmm("attn.qkv")
    grouped = common.dense_group(
        (params["wq"], params["wk"], params["wv"]), x, td)
    for got, name in zip(grouped, ("wq", "wk", "wv")):
        seq = common.dense(params[name], x, td)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seq))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_grouped_ssm_project_matches_sequential_dense(backend):
    """ssm.in_proj's five projections as ONE grouped launch == five
    sequential td_matmul calls, bit for bit (uneven N: z/x are d_inner wide,
    B/C are n_groups*d_state, dt is n_heads)."""
    cfg = smoke(get_config("mamba2-1.3b")).replace(tdvmm_plan=TDVMMPlan(
        rules=(tdvmm_rule("ssm.in_proj", enabled=True, backend=backend),)))
    params = ssm.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model)) * 0.3
    td = cfg.site_tdvmm("ssm.in_proj")
    grouped = ssm._project(params, u, cfg, None)
    widths = {y.shape[-1] for y in grouped}
    assert len(grouped) == 5 and len(widths) > 1   # genuinely uneven N
    for got, name in zip(grouped, ("wz", "wx", "wB", "wC", "wdt")):
        seq = common.dense(params[name], u, td)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seq))


def test_grouped_qkv_train_grads_match_sequential():
    """QAT gradients through the grouped launch equal the sequential path."""
    cfg = _attn_cfg().replace(tdvmm_plan=TDVMMPlan(rules=(
        tdvmm_rule("attn.qkv", enabled=True, backend="jnp"),)))
    params = attention.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, cfg.d_model)) * 0.3
    td = cfg.site_tdvmm("attn.qkv")
    names = ("wq", "wk", "wv")

    def loss_grouped(p, x_):
        ys = common.dense_group(tuple(p[n] for n in names), x_, td)
        return sum(jnp.sum(y ** 2) for y in ys)

    def loss_seq(p, x_):
        return sum(jnp.sum(common.dense(p[n], x_, td) ** 2) for n in names)

    g1, gx1 = jax.grad(loss_grouped, argnums=(0, 1))(params, x)
    g2, gx2 = jax.grad(loss_seq, argnums=(0, 1))(params, x)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-5, atol=1e-6)
    for n in names:
        np.testing.assert_allclose(np.asarray(g1[n]["w"]),
                                   np.asarray(g2[n]["w"]),
                                   rtol=1e-5, atol=1e-6)


def test_swa_ring_buffer_decode():
    """Decode with a rolling window cache == full attention restricted to the
    last `window` tokens."""
    cfg = _attn_cfg(swa_window=8)
    params = attention.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s = 1, 20
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.3
    # reference: full-sequence SWA attention, last position
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = attention.apply_train(params, x, cfg, positions)[:, -1]
    # decode path: prefill s-1 then one decode step
    cache = attention.init_cache(cfg, b, max_len=s, dtype=jnp.float32)
    _, cache = attention.apply_prefill(params, x[:, :-1], cfg, cache)
    out, cache = attention.apply_decode(params, x[:, -1:], cfg, cache)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert int(cache.pos[0]) == s


def test_ragged_decode_positions():
    """Per-sequence cache positions: two sequences decoding at different
    offsets must match their aligned single-sequence runs."""
    cfg = _attn_cfg()
    params = attention.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x1 = jax.random.normal(jax.random.PRNGKey(1), (1, 5, cfg.d_model)) * 0.3
    x2 = jax.random.normal(jax.random.PRNGKey(2), (1, 9, cfg.d_model)) * 0.3
    tok = jax.random.normal(jax.random.PRNGKey(3), (2, 1, cfg.d_model)) * 0.3

    def single(xp, t):
        c = attention.init_cache(cfg, 1, 16, jnp.float32)
        _, c = attention.apply_prefill(params, xp, cfg, c)
        y, _ = attention.apply_decode(params, t, cfg, c)
        return y

    y1 = single(x1, tok[:1])
    y2 = single(x2, tok[1:])
    # batched ragged: merge caches at different positions
    c = attention.init_cache(cfg, 2, 16, jnp.float32)
    c1 = attention.init_cache(cfg, 1, 16, jnp.float32)
    _, c1 = attention.apply_prefill(params, x1, cfg, c1)
    c2 = attention.init_cache(cfg, 1, 16, jnp.float32)
    _, c2 = attention.apply_prefill(params, x2, cfg, c2)
    c = attention.KVCache(
        k=c.k.at[0].set(c1.k[0]).at[1].set(c2.k[0]),
        v=c.v.at[0].set(c1.v[0]).at[1].set(c2.v[0]),
        pos=jnp.array([5, 9], jnp.int32))
    y, _ = attention.apply_decode(params, tok, cfg, c)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y1[0]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(y2[0]), rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 100))
def test_rope_relative_property(seed):
    """<rope(q,p), rope(k,p+d)> depends only on d (relative positions)."""
    d = 32
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (1, 1, 1, d))
    kk = jax.random.normal(jax.random.split(k)[0], (1, 1, 1, d))
    def dot_at(p0, p1):
        qp = common.apply_rope(q, jnp.array([[p0]]), 10000.0)
        kp = common.apply_rope(kk, jnp.array([[p1]]), 10000.0)
        return float(jnp.sum(qp * kp))
    assert dot_at(3, 7) == pytest.approx(dot_at(103, 107), rel=1e-4)


# --------------------------------------------------------------------------
# MoE
# --------------------------------------------------------------------------
def _moe_cfg(**kw):
    cfg = smoke(get_config("mixtral-8x7b"))
    if kw:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, **kw))
    return cfg


def test_moe_dispatch_combine_identity():
    """With no drops, dispatch->identity-experts->combine == weighted passthrough."""
    cfg = _moe_cfg(capacity_factor=64.0)
    m = cfg.moe
    x = jax.random.normal(jax.random.PRNGKey(0), (64, cfg.d_model))
    ids = jax.random.randint(jax.random.PRNGKey(1), (64, m.top_k), 0, m.n_experts)
    gates = jnp.full((64, m.top_k), 1.0 / m.top_k)
    cap = moe._capacity(64, m.top_k, m.n_experts, 64.0)
    se, pos, order, tok = moe._dispatch_indices(ids, m.top_k)
    buf = moe._scatter_to_buffer(x, se, pos, tok, m.n_experts, cap)
    y = moe._gather_from_buffer(buf, se, pos, order, gates, m.top_k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5, atol=1e-6)


def test_moe_capacity_drops_are_zero():
    """Dropped tokens contribute zero (not garbage) to the combined output."""
    cfg = _moe_cfg(capacity_factor=0.01)    # tiny capacity -> mass dropping
    params = moe.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe.apply(params, x, cfg)
    assert jnp.all(jnp.isfinite(y))
    # with capacity ~4 slots/expert most tokens drop; norm must shrink
    cfg_big = _moe_cfg(capacity_factor=64.0)
    y_big, _ = moe.apply(params, x, cfg_big)
    assert float(jnp.linalg.norm(y)) < float(jnp.linalg.norm(y_big))


def test_moe_load_balance_loss_uniform_is_one():
    """LB loss == E * sum(me*ce) -> 1.0 for perfectly uniform routing."""
    cfg = _moe_cfg()
    t, e, k = 1024, cfg.moe.n_experts, cfg.moe.top_k
    probs = jnp.full((t, e), 1.0 / e)
    me = probs.mean(0)
    ids = jnp.stack([(jnp.arange(t) + i) % e for i in range(k)], 1)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(ids, e), axis=1), axis=0)
    lb = e * jnp.sum(me * ce)
    assert float(lb) == pytest.approx(k, rel=1e-5)


def test_moe_grads_flow_to_experts_and_router():
    cfg = _moe_cfg(capacity_factor=8.0)
    params = moe.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))

    def loss(p):
        y, aux = moe.apply(p, x, cfg)
        return jnp.sum(y ** 2) + 0.01 * aux["lb_loss"]

    g = jax.grad(loss)(params)
    assert float(jnp.linalg.norm(g["experts"]["w_up"])) > 0
    assert float(jnp.linalg.norm(g["router"]["w"])) > 0


def test_int8_kv_cache_decode_close_to_full():
    """Perf it.9: int8 KV cache decode must track the full-precision forward."""
    from repro.models import model
    attention.set_kv_cache_int8(True)
    try:
        cfg = smoke(get_config("yi-34b"))
        params = model.init_params(jax.random.PRNGKey(0), cfg)
        b, s = 2, 12
        inputs = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
        full, _ = model.forward(params, {"inputs": inputs, "targets": inputs}, cfg)
        caches = model.init_caches(cfg, b, max_len=s)
        assert caches["seg0"].k.dtype == jnp.int8
        _, caches = model.prefill_step(params, {"inputs": inputs[:, :-1]}, caches, cfg)
        dec, _ = model.decode_step(params, {"inputs": inputs[:, -1:]}, caches, cfg)
        err = float(jnp.max(jnp.abs(full[:, -1] - dec[:, 0])))
        assert err < 0.15, err
    finally:
        attention.set_kv_cache_int8(False)


# --------------------------------------------------------------------------
# SSD
# --------------------------------------------------------------------------
def test_ssd_chunked_equals_naive():
    b, l, h, p, g, s = 2, 64, 4, 16, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, l, h))) * 0.1
    a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
    bb = jax.random.normal(keys[2], (b, l, g, s)) * 0.3
    cc = jax.random.normal(keys[3], (b, l, g, s)) * 0.3
    y1, f1 = ssd_chunked(x, dt, a_log, bb, cc, 16)
    y2, f2 = ssd_naive(x, dt, a_log, bb, cc)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-4, atol=1e-5)


def test_ssm_decode_matches_prefill():
    cfg = smoke(get_config("mamba2-1.3b"))
    params = ssm.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s = 1, 16
    u = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.3
    y_full = ssm.apply_train(params, u, cfg)
    cache = ssm.init_cache(cfg, b, jnp.float32)
    _, cache = ssm.apply_prefill(params, u[:, :-1], cfg, cache)
    y_dec, cache = ssm.apply_decode(params, u[:, -1:], cfg, cache)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]), np.asarray(y_full[:, -1]),
                               rtol=2e-3, atol=2e-3)
    assert int(cache.pos[0]) == s
