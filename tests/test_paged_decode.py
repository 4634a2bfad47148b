"""The decode step's in-place page read (``paged_decode_kernel``, Pallas in
interpret mode here) against the gather path it replaces on a TPU.

``attention._decode_in_place`` (the kernel, then the merge of the step's
own row) has to give what ``attention._decode_gathered`` (every page of
the table gathered, every position scored) gives, to the rounding of
the pools' dtype (``TOL``), at the three decode cells' head layouts: MHA
16 x 64 (qwen1.5-0.5b), GQA 40 / 8 x 128 (qwen2.5-14b) and 32 / 4 x 128
through a window ring (Trinity-Mini).  Positions cover an inactive slot
(0), both sides of page boundaries, a full slot and rings that have
wrapped.  The kernel reads no page it is not given and couples no slot to
another.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.models import attention, model
from repro.runtime.engine import Engine, EngineConfig, Request
from repro.runtime.paged_cache import ring_pages

PAGE, SLOT_PAGES, POOL_PAGES, LAYERS = 16, 6, 40, 2
WINDOW, CHUNK = 64, 32
RING = ring_pages(WINDOW, CHUNK, PAGE)            # 6 pages, 96 positions
# f32: the two paths sum in another order.  bf16 pools and queries: the
# gather path also rounds its scores and its output to bf16, so the two
# differ by a few bf16 steps of O(1) outputs.
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2**-6, atol=2**-5)}
# Slot positions: inactive, a page's last row, a page's first, the next,
# and a full slot (its new row takes the last position of its last page).
FULL_POS = (0, 15, 16, 17, SLOT_PAGES * PAGE - 1)
# Window: before the ring wraps, at a page boundary past the window, once
# wrapped, and wrapped many times.
RING_POS = (0, 15, 48, WINDOW + 40, 5 * RING * PAGE + 7)

LAYOUTS = {"mha-16x64": ("qwen1.5-0.5b", None),
           "gqa-40-8x128": ("qwen2.5-14b", None),
           "ring-32-4x128": ("trinity-mini", WINDOW)}


def _case(name, pos=None, seed=0, dtype=jnp.float32):
    """(cfg, q, k_new, v_new, pools, tables, pos, active, window) of one
    layout in ``dtype``, layer 1 of two stacked layers."""
    arch, window = LAYOUTS[name]
    cfg = get_config(arch).replace(layer_types=None, swa_window=window)
    h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pos = np.asarray(pos or (RING_POS if window else FULL_POS), np.int32)
    b = len(pos)
    rng = np.random.default_rng(seed)
    if window:      # slot s owns ring pages [s * RING, (s + 1) * RING)
        n_pages = b * RING
        tables = np.arange(n_pages, dtype=np.int32).reshape(b, RING)
    else:
        n_pages = POOL_PAGES
        tables = rng.permutation(n_pages)[:b * SLOT_PAGES].reshape(
            b, SLOT_PAGES).astype(np.int32)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    pools = attention.PagedKVCache(
        normal(LAYERS, n_pages + 1, PAGE, n_kv * hd),
        normal(LAYERS, n_pages + 1, PAGE, n_kv * hd))
    return (cfg, normal(b, 1, h, hd), normal(b, n_kv, hd), normal(b, n_kv, hd),
            pools, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(pos > 0), window)


def _in_place(cfg, q, k_new, v_new, pools, tables, pos, active, window):
    return np.asarray(attention._decode_in_place(
        q, k_new, v_new, pools, jnp.int32(1), tables, pos, active, window))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", LAYOUTS)
def test_in_place_read_matches_the_gather(name, dtype):
    cfg, q, k_new, v_new, pools, tables, pos, active, window = _case(
        name, dtype=dtype)
    got = _in_place(cfg, q, k_new, v_new, pools, tables, pos, active, window)
    want = np.asarray(attention._decode_gathered(
        q, k_new, v_new, pools, jnp.int32(1), tables, pos, window, cfg))
    live = np.asarray(active)
    got, want = got.astype(np.float32), want.astype(np.float32)
    np.testing.assert_allclose(got[live], want[live], **TOL[dtype])
    # An inactive slot attends to its own row alone: its value rows.
    groups = cfg.n_heads // cfg.n_kv_heads
    own = np.repeat(np.asarray(v_new.astype(jnp.float32))[~live], groups, 1)
    np.testing.assert_allclose(got[~live][:, 0], own, **TOL[dtype])


@pytest.mark.parametrize("name", ["mha-16x64", "ring-32-4x128"])
def test_in_place_read_reads_no_other_page(name):
    """Every page that holds none of a slot's keys (past its position,
    outside its window, the trash page, the other layer) turned to NaN
    changes nothing: the kernel does not read it."""
    cfg, q, k_new, v_new, pools, tables, pos, active, window = _case(name)
    want = _in_place(cfg, q, k_new, v_new, pools, tables, pos, active, window)
    keep = np.zeros(pools.k.shape[:2], bool)
    for slot, p in enumerate(np.asarray(pos)):
        first = max(p - window + 1, 0) if window else 0
        row = np.asarray(tables)[slot]
        for key in range(first, p):
            keep[1, row[(key // PAGE) % len(row)]] = True
    poisoned = attention.PagedKVCache(
        *(jnp.where(keep[..., None, None], x, jnp.nan) for x in pools[:2]))
    got = _in_place(cfg, q, k_new, v_new, poisoned, tables, pos, active,
                    window)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["gqa-40-8x128", "ring-32-4x128"])
def test_a_slot_is_unchanged_by_the_others(name):
    """Slot 1's output is bitwise the same whatever the other slots'
    positions, activity and pages are."""
    cfg, q, k_new, v_new, pools, tables, pos, active, window = _case(name)
    want = _in_place(cfg, q, k_new, v_new, pools, tables, pos, active, window)
    other = np.asarray(pos).copy()
    other[[0, 2, 3, 4]] = (33, 0, 1, 64)
    shuffled = np.asarray(tables).copy()
    shuffled[[0, 2, 3, 4]] = shuffled[[4, 3, 0, 2]]
    got = _in_place(cfg, q, k_new, v_new, pools, jnp.asarray(shuffled),
                    jnp.asarray(other), jnp.asarray(other > 0), window)
    np.testing.assert_array_equal(got[1], want[1])


def test_window_pages_are_the_rings_pages_of_the_window():
    ring = jnp.arange(2 * RING, dtype=jnp.int32).reshape(2, RING) + 100
    pos = jnp.asarray([20, 5 * RING * PAGE + 7], jnp.int32)
    pages, first = attention._window_pages(ring, pos, WINDOW, PAGE)
    # 63 keys below pos span at most 5 pages of 16.
    assert pages.shape == (2, 5)
    # Slot 0: keys 0..19 on logical pages 0, 1.  Slot 1: keys 424..486,
    # logical pages 26..30, ring entries (26..30) % 6.
    np.testing.assert_array_equal(first, [0, 26])
    np.testing.assert_array_equal(pages[1], [100 + RING + p % RING
                                             for p in range(26, 31)])


# --------------------------------------------------------------------------
# Through the engine: a window-and-full model, its decode steps in place
# --------------------------------------------------------------------------
def _serve(in_place, monkeypatch):
    """Trinity-Mini's layer kinds at smoke size (3 window layers of an
    8-position window, 1 full), 4-position pages, 8-row chunks: prompts of
    6 and 13 tokens, 3 tokens out.  Prefill ticks come first, so both
    decode steps run both slots, at positions 6, 13 and then 7, 14."""
    if in_place:
        monkeypatch.setattr(attention, "reads_in_place", lambda *_: True)
    cfg = smoke(get_config("trinity-mini"))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(slots=2, page_size=4, num_pages=16,
                        max_pages_per_slot=8, chunk=8)
    eng = Engine(cfg, params, ecfg)
    rep = eng.run([Request(rid=i, prompt=tuple(range(1, 7 + 7 * i)),
                           max_new_tokens=3) for i in range(2)])
    assert (rep.prefill_steps, rep.decode_steps) == (3, 2)
    return eng, {r["rid"]: r["tokens"] for r in rep.requests}


def test_engine_decodes_in_place_as_it_gathers(monkeypatch):
    gathered, want = _serve(False, monkeypatch)
    eng, got = _serve(True, monkeypatch)
    assert eng._decode_in_place and not gathered._decode_in_place
    assert got == want
    assert eng.compiled_steps() == 2


def test_decode_counts_the_pages_it_reads(monkeypatch):
    """Prefill chunks gather a block row of 8 pages and a ring of 4
    (``ring_pages(8, 8, 4)``); live after them: 2, 2, 4 pages of each.
    Decode reads only the pages holding keys: the full layer's below
    positions 6, 13, 7, 14 (2 + 4 + 2 + 4), the ring's holding keys 0-5,
    6-12, 0-6, 7-13 (2 + 3 + 2 + 3), all live."""
    gathered, _ = _serve(False, monkeypatch)
    eng, _ = _serve(True, monkeypatch)
    prefill_read, prefill_live, prefill_ring = 3 * (8 + 4), 8 + 8, 3 * 4
    st = eng._st
    assert st.kv_pages_read == prefill_read + 12 + 10
    assert st.kv_pages_live == prefill_live + 12 + 10
    assert st.kv_window_pages_read == prefill_ring + 10
    # Gathered, every slot row reads its whole row and ring.
    st = gathered._st
    assert st.kv_pages_read == prefill_read + 2 * 2 * (8 + 4)
    assert st.kv_window_pages_read == prefill_ring + 2 * 2 * 4
