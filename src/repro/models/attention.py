"""Multi-head attention: GQA/MHA, sliding-window, KV cache prefill/decode.

Optional per model: per-head RMSNorm of q and k (``qk_norm``), an output
gate ``sigmoid(x Wg)`` whose projection joins the ``attn.qkv`` launch
(``attn_gate``), and no rotary embedding on full-attention layers
(``rope_full_layers=False``).  A layer of a model with per-layer kinds
sees the window only where it is a sliding layer
(``transformer.segment_config``).

Weights are stored flattened, (d_model, n_heads*head_dim), so the TP dimension
divides evenly on a 16-way model axis for every assigned arch (e.g. yi-34b's
56 heads x 128 = 7168); GSPMD handles the per-head einsum resharding.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_decode.paged_decode import paged_decode_kernel
from repro.kernels.tdvmm.ops import resolve_backend
from repro.launch import meshctx
from repro.models import common
from repro.runtime.paged_cache import PrefillChunkCtx
from repro.runtime.trace import scope


class KVCache(NamedTuple):
    k: jax.Array          # (B, S_cache, n_kv, head_dim)  bf16 or int8
    v: jax.Array          # (B, S_cache, n_kv, head_dim)
    pos: jax.Array        # (B,) int32 — tokens absorbed per sequence (ragged
    #                       decode: slots advance independently)
    k_scale: jax.Array | None = None   # (B, S_cache, n_kv) — int8 mode only
    v_scale: jax.Array | None = None


# int8 KV cache: half the KV bytes a bandwidth-bound decode step reads.
KV_CACHE_INT8 = False


def set_kv_cache_int8(on: bool):
    global KV_CACHE_INT8
    KV_CACHE_INT8 = on


def _kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., hd) -> int8 codes + per-(token, head) scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-6) / 127.0
    codes = jnp.clip(jnp.round(x / scale[..., None]), -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def _kv_dequantize(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init(key, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    bias = cfg.qkv_bias
    p = {
        "wq": common.dense_init(kq, d, cfg.n_heads * hd, dtype, bias=bias),
        "wk": common.dense_init(kk, d, cfg.n_kv_heads * hd, dtype, bias=bias),
        "wv": common.dense_init(kv, d, cfg.n_kv_heads * hd, dtype, bias=bias),
        "wo": common.dense_init(ko, cfg.n_heads * hd, d, dtype),
    }
    if cfg.attn_gate:
        p["wg"] = common.dense_init(jax.random.fold_in(key, 1), d,
                                    cfg.n_heads * hd, dtype)
    if cfg.qk_norm:
        p["q_norm"] = common.rmsnorm_init(hd, dtype)
        p["k_norm"] = common.rmsnorm_init(hd, dtype)
    return p


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def _qkv(params, x: jax.Array, cfg: ModelConfig, key):
    """q/k/v projections as ONE grouped TD-VMM launch (site ``attn.qkv``).

    The shared input is encoded once and wq/wk/wv run as three tiles of a
    single batched kernel dispatch — the paper's shared-DAC amortization —
    instead of three ``dense`` calls that each re-encode x.  A gated layer's
    wg is a fourth member of the same launch; its output is the gate's
    pre-activation (else None)."""
    td = cfg.site_tdvmm("attn.qkv")
    hd = cfg.resolved_head_dim
    ws = (params["wq"], params["wk"], params["wv"])
    if cfg.attn_gate:
        ws += (params["wg"],)
    q, k, v, *g = common.dense_group(ws, x, td, key)
    return (_split_heads(q, cfg.n_heads, hd),
            _split_heads(k, cfg.n_kv_heads, hd),
            _split_heads(v, cfg.n_kv_heads, hd),
            _split_heads(g[0], cfg.n_heads, hd) if g else None)


def _project(params, x: jax.Array, cfg: ModelConfig, positions, key):
    """q, k, v and the gate of a layer: per-head RMSNorm of q and k
    (``qk_norm``), then the rotary embedding unless the layer attends over
    every position of a model whose full layers take none (NoPE)."""
    q, k, v, g = _qkv(params, x, cfg, key)
    if cfg.qk_norm:
        q = common.rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = common.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.swa_window is not None or cfg.rope_full_layers:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v, g


def _gated(out: jax.Array, g) -> jax.Array:
    """The attention output, times sigmoid of the gate where there is one."""
    return out if g is None else out * jax.nn.sigmoid(g)


def _merge_heads(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


FLASH_THRESHOLD = 2048   # use online-softmax blocked attention above this S
FLASH_BLOCK_Q = 1024
FLASH_BLOCK_KV = 1024


def _attend_flash(q, k, v, cfg: ModelConfig, q_offset: int = 0) -> jax.Array:
    """Blocked causal attention with online softmax (flash-style).

    Never materializes the (Sq, Skv) logits: a double lax.scan over
    (q blocks, kv blocks) carries running (max, denom, acc) — the JAX-level
    equivalent of the VMEM-resident blocking a Pallas kernel would use; XLA
    keeps per-tile buffers at FLASH_BLOCK_Q x FLASH_BLOCK_KV.

    q: (B, Sq, H, D); k, v: (B, Skv, Kv, D).  Causal + optional SWA mask,
    with q global positions offset by q_offset.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    kvh = cfg.n_kv_heads
    g = h // kvh
    bq = min(FLASH_BLOCK_Q, sq)
    bkv = min(FLASH_BLOCK_KV, skv)
    # Non-block-multiple lengths: zero-pad to the block grid and mask the
    # key tail (k_pos < skv); padded query rows compute garbage that the
    # final slice drops.
    sq_real, skv_real = sq, skv
    pad_q, pad_kv = (-sq) % bq, (-skv) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        sq += pad_q
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        skv += pad_kv
    nq, nkv = sq // bq, skv // bkv
    scale = d ** -0.5
    window = cfg.swa_window

    qr = q.reshape(b, nq, bq, kvh, g, d).transpose(1, 0, 3, 4, 2, 5)  # (nq,b,kv,g,bq,d)
    kr = k.reshape(b, nkv, bkv, kvh, d).transpose(1, 0, 3, 2, 4)      # (nkv,b,kv,bkv,d)
    vr = v.reshape(b, nkv, bkv, kvh, d).transpose(1, 0, 3, 2, 4)

    def q_block(_, qi_qb):
        qi, qb = qi_qb                     # qb: (b, kv, g, bq, d)
        q_pos = qi * bq + jnp.arange(bq) + q_offset

        def kv_block(carry, ki_kb):
            m, l, acc = carry
            ki, kb, vb = ki_kb
            k_pos = ki * bkv + jnp.arange(bkv)
            logits = jnp.einsum("bkgqd,bktd->bkgqt", qb, kb).astype(jnp.float32)
            logits *= scale
            mask = k_pos[None, :] <= q_pos[:, None]
            if pad_kv:
                mask &= k_pos[None, :] < skv_real
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, bq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nkv), kr, vr))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qr))
    # outs: (nq, b, kv, g, bq, d) -> (b, sq, h, d); drop padded query rows
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, d)[:, :sq_real]


@scope("attention")
def _attend(q, k, v, mask, cfg: ModelConfig) -> jax.Array:
    """q: (B,Sq,H,D); k,v: (B,Skv,Kv,D); mask: (B,1,Sq,Skv) or broadcastable."""
    hd = q.shape[-1]
    groups = cfg.n_heads // cfg.n_kv_heads
    b, sq, h, _ = q.shape
    skv = k.shape[1]
    q = q.reshape(b, sq, cfg.n_kv_heads, groups, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    logits = logits * (hd ** -0.5)
    logits = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, sq, h, hd)


def _causal_mask(sq: int, skv: int, offset: int, window: Optional[int]) -> jax.Array:
    """(1, 1, sq, skv) boolean mask.  offset = absolute position of query 0."""
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(skv)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None]


def apply_train(params, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
                key=None) -> jax.Array:
    """Full-sequence causal (optionally sliding-window) attention."""
    q, k, v, g = _project(params, x, cfg, positions, key)
    s = x.shape[1]
    if s > FLASH_THRESHOLD:
        out = _attend_flash(q, k, v, cfg)
    else:
        mask = _causal_mask(s, s, 0, cfg.swa_window)
        out = _attend(q, k, v, mask, cfg)
    return common.dense(params["wo"], _merge_heads(_gated(out, g)),
                        cfg.site_tdvmm("attn.out"), key)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> KVCache:
    """Rolling cache of size min(max_len, window) for SWA archs."""
    size = max_len if cfg.swa_window is None else min(max_len, cfg.swa_window)
    shape = (batch, size, cfg.n_kv_heads, cfg.resolved_head_dim)
    if KV_CACHE_INT8:
        sshape = shape[:-1]
        return KVCache(jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                       jnp.zeros((batch,), jnp.int32),
                       jnp.zeros(sshape, jnp.float32),
                       jnp.zeros(sshape, jnp.float32))
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((batch,), jnp.int32))


def apply_prefill(params, x: jax.Array, cfg: ModelConfig, cache: KVCache,
                  key=None) -> tuple[jax.Array, KVCache]:
    """Process a full prompt, filling the cache (assumes cache.pos == 0)."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v, g = _project(params, x, cfg, positions, key)
    if s > FLASH_THRESHOLD:
        out = _attend_flash(q, k, v, cfg)
    else:
        mask = _causal_mask(s, s, 0, cfg.swa_window)
        out = _attend(q, k, v, mask, cfg)

    size = cache.k.shape[1]
    k_store, v_store = k, v
    k_sc = v_sc = None
    if cache.k_scale is not None:
        k_store, k_sc = _kv_quantize(k)
        v_store, v_sc = _kv_quantize(v)
    if size >= s:
        new_k = jax.lax.dynamic_update_slice(
            cache.k, k_store.astype(cache.k.dtype), (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            cache.v, v_store.astype(cache.v.dtype), (0, 0, 0, 0))
        if k_sc is not None:
            k_sc = jax.lax.dynamic_update_slice(cache.k_scale, k_sc, (0, 0, 0))
            v_sc = jax.lax.dynamic_update_slice(cache.v_scale, v_sc, (0, 0, 0))
    else:  # rolling SWA cache keeps the last `size` tokens, ring-aligned so that
        # absolute position p lives at slot p % size (what decode expects).
        shift = s % size
        new_k = jnp.roll(k_store[:, -size:], shift, axis=1).astype(cache.k.dtype)
        new_v = jnp.roll(v_store[:, -size:], shift, axis=1).astype(cache.v.dtype)
        if k_sc is not None:
            k_sc = jnp.roll(k_sc[:, -size:], shift, axis=1)
            v_sc = jnp.roll(v_sc[:, -size:], shift, axis=1)
    new_cache = KVCache(new_k, new_v, jnp.full((b,), s, jnp.int32), k_sc, v_sc)
    y = common.dense(params["wo"], _merge_heads(_gated(out, g)),
                     cfg.site_tdvmm("attn.out"), key)
    return y, new_cache


# --------------------------------------------------------------------------
# Paged KV cache (serving engine): block-table-indexed pages instead of a
# dense (B, max_len) buffer.  See runtime/paged_cache.py for the layout and
# the trash-page convention; the engine (runtime/engine.py) owns allocation.
# --------------------------------------------------------------------------
class PagedKVCache(NamedTuple):
    """One layer's page pool; stacked (L, ...) in the step programs.  The
    same tuple carries a step's new rows: (R, n_kv * head_dim) leaves.

    A position's heads sit side by side in one minor dim: with a head_dim
    under 128 (qwen1.5-0.5b's 64) a (..., n_kv, head_dim) pool is laid out
    page-minor by a TPU (to save padding), and its page gathers and row
    writes then relay the whole pool out and back every step."""
    k: jax.Array          # (num_pages+1, page_size, n_kv * head_dim); last
    #                       page is the write sink for padded/inactive rows
    v: jax.Array
    k_scale: jax.Array | None = None   # (num_pages+1, page_size, n_kv) int8 mode
    v_scale: jax.Array | None = None


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype, ranks: int = 1) -> PagedKVCache:
    """One attention layer's page pool (+1 trash page per rank).  Honors the
    same KV_CACHE_INT8 switch as the dense cache.  ``ranks > 1`` stacks one
    ``num_pages + 1`` region per DP rank (see ``runtime.paged_cache.PagePool``
    for the global page-id arithmetic).  A window layer's ring pool has the
    same layout, with its caller's count of ring pages."""
    shape = (ranks * (num_pages + 1), page_size,
             cfg.n_kv_heads * cfg.resolved_head_dim)
    if KV_CACHE_INT8:
        sshape = shape[:-1] + (cfg.n_kv_heads,)
        return PagedKVCache(jnp.zeros(shape, jnp.int8),
                            jnp.zeros(shape, jnp.int8),
                            jnp.zeros(sshape, jnp.float32),
                            jnp.zeros(sshape, jnp.float32))
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _paged_targets(ctx, pools: PagedKVCache, r: int, ring: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """Where this step's R rows go: (page id, offset) per row, (R,) each.

    Every layer shares them (pages are allocated per slot, not per layer).
    ``ctx`` is a ``PrefillChunkCtx`` (R = C chunk rows; rows past ``valid``
    go to the trash page) or a ``DecodeCtx`` (R = B slots; inactive slots
    go to the trash page).  With ``ring`` the rows go to the slot's window
    ring: position p to ring page ``(p // page_size) % ring_pages``."""
    ps = pools.k.shape[2]
    trash = pools.k.shape[1] - 1

    def page(pos, n_rows):
        return (pos // ps) % n_rows if ring else jnp.minimum(pos // ps, n_rows - 1)

    if isinstance(ctx, PrefillChunkCtx):
        gpos = ctx.offset + jnp.arange(r, dtype=jnp.int32)
        row = ctx.ring_row if ring else ctx.block_row
        pid = row[page(gpos, row.shape[0])]
        return jnp.where(jnp.arange(r) < ctx.valid, pid, trash), gpos % ps
    tables = ctx.ring_tables if ring else ctx.block_tables
    page_idx = page(ctx.pos, tables.shape[1])
    pid = jnp.take_along_axis(tables, page_idx[:, None], 1)[:, 0]
    return jnp.where(ctx.active, pid, trash), ctx.pos % ps


def _ring_positions(size: int, end) -> jax.Array:
    """The position each of a window ring's ``size`` entries holds in a step
    that has written every position below ``end``: the latest p < end with
    p = entry (mod size); negative where the slot has no such position yet."""
    j = jnp.arange(size, dtype=jnp.int32)
    return end - 1 - jnp.remainder(end - 1 - j, size)


@scope("kv.write")
def paged_write(pools: PagedKVCache, rows: PagedKVCache, ctx,
                ring: bool = False) -> PagedKVCache:
    """Write every layer's new rows into the stacked pools, once, after the
    layer scan: ``rows`` holds (L, R, ...) leaves (``_fresh_rows`` of each
    layer), ``pools`` the donated (L, pages, page_size, ...) pools, and row
    r of layer l goes to ``(l, *_paged_targets(ctx, pools, R, ring)[r])``.
    One scatter per pool, in place on the donated pool (why not inside the
    scan: see ``transformer._scan_segment``)."""
    pid, off = _paged_targets(ctx, pools, rows.k.shape[1], ring)
    layer = jnp.arange(pools.k.shape[0], dtype=jnp.int32)[:, None]

    def write(buf, val):
        if buf is None:
            return None
        return buf.at[layer, pid[None], off[None]].set(val.astype(buf.dtype))

    return PagedKVCache(*(write(b, r) for b, r in zip(pools, rows)))


@scope("kv.write")
def _fresh_rows(pools: PagedKVCache, k, v, dtype):
    """This layer's new rows as the pools store them (int8 codes and
    scales under KV_CACHE_INT8), and as a read of the pools would return
    them in ``dtype``: k, v (R, n_kv, head_dim)."""
    def flat(x):
        return x.reshape(x.shape[0], -1)

    if pools.k_scale is None:
        k, v = k.astype(pools.k.dtype), v.astype(pools.v.dtype)
        return PagedKVCache(flat(k), flat(v)), k.astype(dtype), v.astype(dtype)
    k_q, k_s = _kv_quantize(k)
    v_q, v_s = _kv_quantize(v)
    return (PagedKVCache(flat(k_q), flat(v_q), k_s, v_s),
            _kv_dequantize(k_q, k_s, dtype), _kv_dequantize(v_q, v_s, dtype))


@scope("kv.read")
def _paged_read(pools: PagedKVCache, layer, tables, k_new, v_new, src, sel,
                dtype):
    """Gather layer ``layer``'s pages of a slot into position order, with
    this step's new rows selected in where ``sel`` holds.

    pools: the stacked (L, pages, ...) pools, read in place (no layer's
    pool is sliced out); tables: (N, P) page ids; k_new / v_new: the R new
    rows as a read returns them; src, sel: (N, P*page_size) or
    broadcastable, the new row at each key position and whether to take
    it.  The new rows are not in the pools yet (``paged_write`` puts them
    there after the layer scan), so they are merged by position with an
    elementwise ``where``: every key keeps its position and exactly the
    value a read after the write would give, so attention reduces in the
    same order as the dense path, bit for bit."""
    heads = tables.shape[:1] + (-1,) + k_new.shape[-2:]   # (N, P*ps, kv, hd)
    k_read = pools.k[layer, tables].reshape(heads)
    v_read = pools.v[layer, tables].reshape(heads)
    if pools.k_scale is not None:
        ks = pools.k_scale[layer, tables].reshape(heads[:-1])
        vs = pools.v_scale[layer, tables].reshape(heads[:-1])
        k_read = _kv_dequantize(k_read, ks, dtype)
        v_read = _kv_dequantize(v_read, vs, dtype)
    sel = sel[..., None, None]
    return (jnp.where(sel, k_new[src], k_read.astype(dtype)),
            jnp.where(sel, v_new[src], v_read.astype(dtype)))


def reads_in_place(pools: PagedKVCache, mesh) -> bool:
    """Whether a decode step reads its pages in place through the Pallas
    kernel (``_paged_read_in_place``) rather than by gathering them
    (``_decode_gathered``): on a TPU (``"auto"`` resolved as the TD-VMM
    kernels resolve it), for pools the kernel reads (no int8 KV: it reads
    no scales), outside a multi-device ``mesh`` (GSPMD cannot partition a
    Mosaic call), and for pages of whole native tiles (rows a multiple of
    the dtype's sublanes, a lane-aligned row).  ``pools`` may hold shapes.
    The gather path is the reference and the CPU path."""
    page_size, width = pools.k.shape[-2:]
    return (resolve_backend("auto") == "pallas" and pools.k_scale is None
            and (mesh is None or mesh.size == 1)
            and page_size % (32 // pools.k.dtype.itemsize) == 0
            and width % 128 == 0)


def _window_pages(ring: jax.Array, pos: jax.Array, window: int,
                  page_size: int) -> tuple[jax.Array, jax.Array]:
    """A window layer's decode read: each slot's ring pages that hold its
    keys in ``(pos - window, pos)``, in position order ((B, n) page ids,
    n the most pages ``window - 1`` positions span), and the logical page
    of the first of them, (B,).  Position p lives on ring page
    ``(p // page_size) % ring_pages``."""
    n_ring = ring.shape[1]
    n = min(n_ring, -(-(window - 1) // page_size) + 1)
    first = jnp.maximum(pos - window + 1, 0) // page_size
    logical = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None]
    return jnp.take_along_axis(ring, logical % n_ring, axis=1), first


@scope("kv.read")
def _paged_read_in_place(q, pools: PagedKVCache, layer, tables, pos, active,
                         window: Optional[int]):
    """Layer ``layer``'s attention of each active slot's query q (B, H, hd)
    over the keys its pages hold below ``pos`` (a window layer: in the
    window), read in place from the stacked pools by
    ``paged_decode_kernel``: only the pages holding such keys, nothing
    copied in HBM.  Returns the kernel's (m, l, acc)."""
    b, h, hd = q.shape
    page_size, width = pools.k.shape[-2:]
    n_kv = width // hd
    if window is None:
        pages, first = tables, jnp.zeros_like(pos)
    else:
        pages, first = _window_pages(tables, pos, window, page_size)
    count = jnp.where(active, -(-pos // page_size) - first, 0)
    own = (jnp.arange(h)[:, None] // (h // n_kv)) == jnp.arange(n_kv)[None]
    q_bd = jnp.where(own[None, :, :, None], q[:, :, None, :], 0)
    return paged_decode_kernel(
        q_bd.reshape(b, h, width), pools.k, pools.v, layer, pages,
        first * page_size, count, pos, head_dim=hd, window=window,
        interpret=resolve_backend("auto") != "pallas")


@scope("attention")
def _merge_fresh(m, l, acc, q, k_new, v_new):
    """The in-place read's online softmax with the step's own row merged
    in (it is in no pool yet: ``paged_write`` stores it after the layer
    scan) by one log-sum-exp step, then normalised.  m, l: (B, H, 1);
    acc: (B, H, hd); q: (B, H, hd); k_new, v_new: (B, n_kv, hd).  Returns
    (B, H, hd)."""
    b, h, hd = q.shape
    n_kv = k_new.shape[1]
    groups = h // n_kv
    s = jnp.einsum("bkgd,bkd->bkg", q.reshape(b, n_kv, groups, hd), k_new,
                   preferred_element_type=jnp.float32)
    s = s.reshape(b, h, 1) * hd ** -0.5
    m_new = jnp.maximum(m, s)
    alpha, e = jnp.exp(m - m_new), jnp.exp(s - m_new)
    v_h = jnp.repeat(v_new, groups, axis=1).astype(jnp.float32)
    out = (alpha * acc + e * v_h) / (alpha * l + e)
    return out.astype(q.dtype)


def apply_prefill_paged(params, x: jax.Array, cfg: ModelConfig,
                        pools: PagedKVCache, layer, ctx, key=None
                        ) -> tuple[jax.Array, PagedKVCache]:
    """One fixed-size prefill chunk for ONE slot, one layer of the
    engine's compiled prefill step.  x: (1, C, d); pools: the stacked page
    pools, read only; layer: this layer's index into them; ctx:
    runtime.paged_cache.PrefillChunkCtx.  Returns the attention output and
    this layer's C new rows, which ``paged_write`` stores after the layer
    scan.

    Tokens [offset, offset + valid) of the slot's prompt are projected,
    rope'd at their global positions, and attended against every page the
    slot owns (earlier chunks included) with the chunk's own rows merged in
    at their positions, under the global causal mask.  Rows past ``valid``
    are padding: their outputs are garbage the engine drops and their rows
    go to the trash page.  The last chunk can reach past the block row
    (offset + C > P * page_size): the merge indexes the chunk by clipped
    position, so those rows are never read.  Bit-for-bit identical to
    ``apply_prefill`` on the whole prompt when the chunk covers it AND the
    cache is not int8-quantized (per-row encode/attend; masked tail keys
    contribute exact zeros).  A window layer (``cfg.swa_window``) reads and
    writes its slot's window ring instead (``ctx.ring_row``): each ring
    entry's position comes from ``_ring_positions``, and keys older than
    the window are masked.  Under KV_CACHE_INT8 this path attends over
    the quantize->dequantize KV (earlier chunks can only be read back
    dequantized), whereas dense ``apply_prefill`` attends over the
    full-precision k/v before storing — the engine's isolation contract is
    therefore engine-vs-solo-engine in int8 mode, not engine-vs-dense."""
    _, c, _ = x.shape
    ps = pools.k.shape[2]
    window = cfg.swa_window
    row = ctx.block_row if window is None else ctx.ring_row
    n_rows = row.shape[0]
    gpos = ctx.offset + jnp.arange(c, dtype=jnp.int32)       # (C,) global
    positions = gpos[None]
    q, k, v, g = _project(params, x, cfg, positions, key)

    rows, k_new, v_new = _fresh_rows(pools, k[0], v[0], q.dtype)
    if window is None:
        kpos = jnp.arange(n_rows * ps, dtype=jnp.int32)
    else:
        kpos = _ring_positions(n_rows * ps, ctx.offset + c)
    end = ctx.offset + ctx.valid
    sel = (kpos >= ctx.offset) & (kpos < end)
    src = jnp.clip(kpos - ctx.offset, 0, c - 1)
    k_read, v_read = _paged_read(pools, layer, row[None], k_new,
                                 v_new, src[None], sel[None], q.dtype)
    mask = (kpos[None, :] <= gpos[:, None]) & (kpos[None, :] < end)
    if window is not None:
        mask &= (kpos[None, :] >= 0) & (kpos[None, :] > gpos[:, None] - window)
    out = _attend(q, k_read, v_read, mask[None, None], cfg)
    y = common.dense(params["wo"], _merge_heads(_gated(out, g)),
                     cfg.site_tdvmm("attn.out"), key)
    return y, rows


def apply_decode_paged(params, x: jax.Array, cfg: ModelConfig,
                       pools: PagedKVCache, layer, ctx, key=None
                       ) -> tuple[jax.Array, PagedKVCache]:
    """Batched one-token decode over all B slots, one layer of the engine's
    compiled decode step.  x: (B, 1, d); pools, layer as in
    ``apply_prefill_paged``; ctx: runtime.paged_cache.DecodeCtx.  Returns
    the attention output and this layer's B new rows, which
    ``paged_write`` stores after the layer scan.

    Each slot attends over its own pages (a window layer: its window ring,
    ``ctx.ring_tables``) with its new row merged in at position ``pos``:
    on a TPU read in place, only the pages that hold keys
    (``_decode_in_place``), else gathered whole (``_decode_gathered``,
    ``reads_in_place`` chooses); inactive slots' rows go to the trash page,
    never advance, and produce ignored outputs.  There is NO
    decode-past-capacity poisoning path here: the engine evicts a request
    *before* its next write would overflow its page budget, so an
    overflowing write can never corrupt (or NaN) a neighbor slot."""
    window = cfg.swa_window
    tables = ctx.block_tables if window is None else ctx.ring_tables
    pos = ctx.pos
    q, k, v, g = _project(params, x, cfg, pos[:, None], key)

    rows, k_new, v_new = _fresh_rows(pools, k[:, 0], v[:, 0], q.dtype)
    if reads_in_place(pools, meshctx.get_mesh()):
        out = _decode_in_place(q, k_new, v_new, pools, layer, tables, pos,
                               ctx.active, window)
    else:
        out = _decode_gathered(q, k_new, v_new, pools, layer, tables, pos,
                               window, cfg)
    y = common.dense(params["wo"], _merge_heads(_gated(out, g)),
                     cfg.site_tdvmm("attn.out"), key)
    return y, rows


def _decode_in_place(q, k_new, v_new, pools: PagedKVCache, layer, tables,
                     pos, active, window: Optional[int]) -> jax.Array:
    """A decode step's attention, its pages read in place: q (B, 1, H, hd),
    k_new / v_new (B, n_kv, hd) the step's rows as a read returns them.
    Returns (B, 1, H, hd)."""
    m, l, acc = _paged_read_in_place(q[:, 0], pools, layer, tables, pos,
                                     active, window)
    return _merge_fresh(m, l, acc, q[:, 0], k_new, v_new)[:, None]


def _decode_gathered(q, k_new, v_new, pools: PagedKVCache, layer, tables,
                     pos, window: Optional[int], cfg: ModelConfig
                     ) -> jax.Array:
    """``_decode_in_place`` by gathering every page of each slot's table
    (a window layer: its whole ring) and scoring every position of them."""
    n_pos = tables.shape[1] * pools.k.shape[2]
    if window is None:
        kpos = jnp.arange(n_pos, dtype=jnp.int32)
        sel = kpos[None, :] == pos[:, None]                   # (B, cap)
    else:
        kpos = _ring_positions(n_pos, pos[:, None] + 1)       # (B, cap)
        sel = kpos == pos[:, None]
    src = jnp.arange(pos.shape[0])[:, None]                   # (B, 1)
    k_read, v_read = _paged_read(pools, layer, tables, k_new,
                                 v_new, src, sel, q.dtype)
    if window is None:
        mask = kpos[None, :] <= pos[:, None]
    else:   # the ring holds no position past pos
        mask = (kpos >= 0) & (kpos > pos[:, None] - window)
    return _attend(q, k_read, v_read, mask[:, None, None, :], cfg)  # (B,1,1,cap)


def apply_decode(params, x: jax.Array, cfg: ModelConfig, cache: KVCache,
                 key=None) -> tuple[jax.Array, KVCache]:
    """One-token decode step.  x: (B, 1, d)."""
    b = x.shape[0]
    pos = cache.pos                                      # (B,) int32
    positions = pos[:, None]                             # (B, 1)
    q, k, v, g = _project(params, x, cfg, positions, key)

    size = cache.k.shape[1]
    if cfg.swa_window is not None:
        slot = pos % size            # ring buffer: every position has a slot
        over = None
    else:
        # A full (non-rolling) cache has exactly `size` slots.  Decoding past
        # capacity used to silently pin slot = size-1, overwriting the last
        # KV entry every step and corrupting attention from then on.  With
        # concrete positions (eager serving) this now raises; under a jit
        # trace the overflowing rows drop their cache write, stop advancing
        # ``pos``, and poison their outputs with NaN — failing loudly
        # instead of decoding against a corrupted cache.
        over = pos >= size
        try:
            if bool(jnp.any(over)):
                raise ValueError(
                    f"attention.apply_decode: KV cache capacity exceeded "
                    f"(pos={pos} >= size={size}); grow max_len or use a "
                    "sliding-window config")
            over = None
        except jax.errors.ConcretizationTypeError:
            pass
        slot = jnp.minimum(pos, size - 1)
    rows = jnp.arange(b)

    def write(buf, val):
        """Write this step's (B, ...) entry to each row's slot; overflowed
        rows re-write the slot's existing value (cache left untouched)."""
        val = val.astype(buf.dtype)
        if over is not None:
            keep = over.reshape((-1,) + (1,) * (val.ndim - 1))
            val = jnp.where(keep, buf[rows, slot], val)
        return buf.at[rows, slot].set(val)

    k_sc = v_sc = None
    if cache.k_scale is not None:
        k_q, k_s1 = _kv_quantize(k)
        v_q, v_s1 = _kv_quantize(v)
        new_k = write(cache.k, k_q[:, 0])
        new_v = write(cache.v, v_q[:, 0])
        k_sc = write(cache.k_scale, k_s1[:, 0])
        v_sc = write(cache.v_scale, v_s1[:, 0])
        k_read = _kv_dequantize(new_k, k_sc, q.dtype)
        v_read = _kv_dequantize(new_v, v_sc, q.dtype)
    else:
        new_k = write(cache.k, k[:, 0])
        new_v = write(cache.v, v[:, 0])
        k_read = new_k.astype(q.dtype)
        v_read = new_v.astype(q.dtype)

    kpos = jnp.arange(size)
    if cfg.swa_window is not None:
        # ring buffer: valid entries were written within the last `size` steps
        age = (slot[:, None] - kpos[None, :]) % size
        valid = age <= jnp.minimum(pos, size - 1)[:, None]
    else:
        valid = kpos[None, :] <= pos[:, None]
    mask = valid[:, None, None, :]                       # (B, 1, 1, S)
    out = _attend(q, k_read, v_read, mask, cfg)
    y = common.dense(params["wo"], _merge_heads(_gated(out, g)),
                     cfg.site_tdvmm("attn.out"), key)
    pos_next = pos + 1
    if over is not None:
        y = jnp.where(over[:, None, None], jnp.float32(jnp.nan).astype(y.dtype), y)
        pos_next = jnp.where(over, pos, pos_next)
    return y, KVCache(new_k, new_v, pos_next, k_sc, v_sc)
