"""Pallas TPU kernel: one decode step's attention over the paged KV pools,
read in place.

The serving engine keeps each attention layer's KV in a page pool stacked
over the layers of a scan segment, ``(L, pages + 1, page_size, n_kv *
head_dim)`` (``models.attention.PagedKVCache``).  A decode step's query of
slot ``b`` attends over the positions ``[start_b, pos_b)`` that slot has
written, which sit on ``count_b`` pages listed in position order by
``pages[b]`` (the block-table row of a full layer; for a window layer the
ring pages that hold the window, ``models.attention._window_pages``).

Grid = (B,), one slot a step, run in order.  A slot's pages are DMA'd from
HBM straight into a double buffer of ``ppb`` pages (one copy a page: pages
of one slot are scattered over the pool), and the next block, or the next
reading slot's first block, is in flight while the current one is scored,
so the DMA engine never waits on a slot boundary.  Only the ``count_b``
pages are copied; a slot that reads none (inactive) costs a grid step and
no DMA.  Nothing of the pool is copied or reshaped in HBM.

Heads work in the pool's row layout: the query enters block-diagonal,
``q_bd[h, kv(h) * hd + d] = q[h, d]`` with ``kv(h) = h // (H / n_kv)``, so a
block's scores are one ``(H, n_kv * hd) x (T, n_kv * hd)^T`` matmul and its
weighted values one ``(H, T) x (T, n_kv * hd)`` matmul, T = ``ppb *
page_size`` rows; head h's output is lane block ``kv(h)`` of its row of the
accumulator, taken in VMEM when the slot is done.  The MXU does n_kv times
the useful products, which costs nothing here: the step is bound by the
pages' bytes.

Scores, running max, sum and the weighted values accumulate in f32 (the
probabilities enter the value matmul in the pool's dtype, as the gather
path's softmax output does).  The kernel returns the online softmax's
``(m, l, acc)`` per head, unnormalised, so that the caller can merge the
step's own row, which is not in the pool yet, by one log-sum-exp step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK = -1e30            # score of a masked key; also the empty running max
BLOCK_BYTES = 1 << 18   # bytes of k a double-buffer half aims at


def _pages_per_block(page_size: int, width: int, itemsize: int,
                    max_pages: int) -> int:
    """Pages a grid block reads: about ``BLOCK_BYTES`` of k (one 16-row
    page of 32 KB is too small a DMA to reach HBM bandwidth), never more
    than a slot can read."""
    return max(1, min(max_pages, BLOCK_BYTES // (page_size * width * itemsize)))


def _kernel(meta_ref, start_ref, count_ref, pos_ref, nxt_ref, pages_ref,
            q_ref, k_hbm, v_hbm, m_ref, l_ref, o_ref,
            k_buf, v_buf, acc_ref, sem, cur_ref, *, ppb: int, n_pages: int,
            width: int, head_dim: int, window: int | None, scale: float):
    b = pl.program_id(0)
    layer, head = meta_ref[0], meta_ref[1]
    ps = k_buf.shape[2]
    rows = ppb * ps

    def each_copy(slot, blk, half, act):
        """``act`` on each copy of block ``blk`` of ``slot`` into buffer
        half ``half``: a k and a v copy a page that the slot reads."""
        n = jnp.minimum(count_ref[slot] - blk * ppb, ppb)
        for j in range(ppb):
            @pl.when(j < n)
            def _():
                page = pages_ref[slot * n_pages + blk * ppb + j]
                for s, (buf, hbm) in enumerate(((k_buf, k_hbm),
                                                (v_buf, v_hbm))):
                    act(pltpu.make_async_copy(hbm.at[layer, page],
                                              buf.at[half, j], sem.at[s, half]))

    def start(slot, blk, half):
        each_copy(slot, blk, half, lambda c: c.start())

    @pl.when(b == 0)
    def _():
        # Pages past a slot's last keep what an earlier block left there;
        # zeros the first time, so a masked row never meets a NaN pattern.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        cur_ref[0] = 0

    @pl.when(b == head)
    def _():
        start(b, 0, cur_ref[0])

    m_ref[...] = jnp.full_like(m_ref, MASK)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    pos = pos_ref[b]
    nblk = pl.cdiv(count_ref[b], ppb)
    q = q_ref[...]

    def block(i, carry):
        half = cur_ref[0]
        nxt = nxt_ref[b]

        @pl.when(i + 1 < nblk)
        def _():
            start(b, i + 1, 1 - half)

        @pl.when((i + 1 == nblk) & (nxt >= 0))
        def _():
            start(nxt, 0, 1 - half)

        each_copy(b, i, half, lambda c: c.wait())
        k = k_buf[half].reshape(rows, width)
        v = v_buf[half].reshape(rows, width)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = (start_ref[b] + i * rows
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        valid = kpos < pos
        if window is not None:
            valid &= kpos > pos - window
        s = jnp.where(valid, s, MASK)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        cur_ref[0] = 1 - half
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    # Head h's values are lane block kv(h) of its accumulator row.
    groups = acc_ref.shape[0] * head_dim // width
    kv = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0) // groups
    out = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(width // head_dim):
        out = jnp.where(kv == j, acc_ref[:, j * head_dim:(j + 1) * head_dim],
                        out)
    o_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("head_dim", "window", "interpret"))
def paged_decode_kernel(q_bd, k_pool, v_pool, layer, pages, start, count, pos,
                        *, head_dim: int, window: int | None = None,
                        interpret: bool = False):
    """Online-softmax attention of each slot's query over its pages.

    q_bd: (B, H, W) block-diagonal queries (W = n_kv * head_dim, module
    docstring); k_pool, v_pool: the stacked (L, pages + 1, page_size, W)
    pools; layer: () int32, the layer read; pages: (B, P) int32, each slot's
    pages in position order; start: (B,) int32, the position of row 0 of
    ``pages[b, 0]``; count: (B,) int32, how many pages slot b reads (0:
    none); pos: (B,) int32, keys at positions >= pos are masked; window:
    keys at positions <= pos - window are masked too.

    Returns (m, l, acc): (B, H, 1), (B, H, 1), (B, H, head_dim) f32 — the
    running max of the scaled scores (``MASK`` where no key was read), the
    sum of ``exp(score - m)`` and the sum of ``exp(score - m) * v``."""
    b, h, width = q_bd.shape
    n_pages = pages.shape[1]
    ps = k_pool.shape[2]
    ppb = _pages_per_block(ps, width, k_pool.dtype.itemsize, n_pages)
    count = count.astype(jnp.int32)
    # nxt[b]: the next slot after b that reads a page (-1: none), whose
    # first block slot b's last block prefetches; head: the first such slot.
    idx = jnp.arange(b, dtype=jnp.int32)
    first_from = jax.lax.cummin(jnp.where(count > 0, idx, b), reverse=True)
    nxt = jnp.append(first_from[1:], b)
    nxt = jnp.where(nxt < b, nxt, -1)
    head = jnp.where(first_from[0] < b, first_from[0], -1)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), head])
    slot = lambda i, *_: (i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b,),
        in_specs=[pl.BlockSpec((None, h, width), slot),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((None, h, 1), slot),
                   pl.BlockSpec((None, h, 1), slot),
                   pl.BlockSpec((None, h, head_dim), slot)],
        scratch_shapes=[pltpu.VMEM((2, ppb, ps, width), k_pool.dtype),
                        pltpu.VMEM((2, ppb, ps, width), v_pool.dtype),
                        pltpu.VMEM((h, width), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)])
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_kernel, ppb=ppb, n_pages=n_pages, width=width,
                          head_dim=head_dim, window=window,
                          scale=head_dim ** -0.5),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, 1), f32),
                   jax.ShapeDtypeStruct((b, h, 1), f32),
                   jax.ShapeDtypeStruct((b, h, head_dim), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode_kernel",
    )(meta, start.astype(jnp.int32), count, pos.astype(jnp.int32), nxt,
      pages.astype(jnp.int32).reshape(-1), q_bd, k_pool, v_pool)
