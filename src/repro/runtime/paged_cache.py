"""Paged KV cache plumbing: page pool, block tables, and step contexts.

The serving engine replaces the dense per-slot ``init_caches(batch, max_len)``
allocation with a **page pool**: attention KV lives in fixed-size pages of
``page_size`` token positions, and every decode slot owns an ordered list of
page ids (its *block-table row*).  Position ``p`` of a slot lives at
``(row[p // page_size], p % page_size)`` — the same page ids index every
layer's pool, so allocation happens once per slot, not per layer.

Why this matters for the TD-VMM story: the analog tiles are weight-stationary
and the conversion circuitry is fixed, so serving wants ONE compiled prefill
step and ONE compiled decode step with pinned shapes (pinned readout windows
ride along as jit-static calibration).  Paging is what lets ragged requests
multiplex through those fixed shapes without paying ``batch * max_len`` HBM
for every short request: a finished request's pages go back to the pool and
the next request reuses them.

Layout per attention layer (see ``models.attention.init_paged_cache``):

    k, v        (num_pages + 1, page_size, n_kv * head_dim)
    k/v_scale   (num_pages + 1, page_size, n_kv)            int8 KV mode

A window layer (sliding attention) keeps its KV in a second pool of the
same layout, the **window ring**: each slot owns ``ring_pages`` pages of it
(slot ``s``: ring pages ``[s * R, (s + 1) * R)``, fixed, so no allocator),
and position ``p`` lives at ``(ring_row[(p // page_size) % R], p %
page_size)``.  The ring holds the window plus one chunk, so a window
layer's KV stays ``R`` pages a slot however long the request.

The **last** page is the trash page: writes from inactive slots (and padded
prefill-chunk rows) are steered there instead of being predicated out, so the
compiled step has no data-dependent control flow.  The trash page is never
read (no block-table row references it as a *valid* position), so its
nondeterministic contents never touch logits.

Under a data-parallel mesh the pool grows a leading rank dimension
(``PagePool(..., ranks=dp)``): the device layout stacks ``ranks`` copies of
the ``num_pages + 1`` region and the global trash page is the last row of the
last rank — see the ``PagePool`` docstring for the id arithmetic.

Host side, ``PagePool`` is a deterministic free-list allocator (lowest free
id first) that tracks the in-use high-water mark — the paged counterpart of
the dense path's ``batch * max_len`` footprint, asserted smaller on ragged
traces by ``tests/test_engine.py``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax


class PrefillChunkCtx(NamedTuple):
    """Per-chunk step inputs for one slot's chunked prefill (fixed shapes).

    block_row: (P,) int32 — the slot's page ids, padded with the trash page.
    offset:    ()   int32 — global position of the chunk's first token.
    valid:     ()   int32 — real tokens in this chunk (rest is padding).
    ring_row:  (R,) int32 — the slot's window-ring page ids (models with
                            window layers; None otherwise).
    """
    block_row: jax.Array
    offset: jax.Array
    valid: jax.Array
    ring_row: Optional[jax.Array] = None


class DecodeCtx(NamedTuple):
    """Per-step inputs for the batched decode over all B slots.

    block_tables: (B, P) int32 — page ids per slot (trash-padded).
    pos:          (B,)   int32 — tokens already absorbed per slot (the new
                                 token's KV is written at position ``pos``).
    active:       (B,)   bool  — occupied decode slots; inactive rows write
                                 to the trash page and their logits are
                                 ignored by the engine.
    ring_tables:  (B, R) int32 — each slot's window-ring page ids (models
                                 with window layers; None otherwise).
    """
    block_tables: jax.Array
    pos: jax.Array
    active: jax.Array
    ring_tables: Optional[jax.Array] = None


class PagePool:
    """Deterministic host-side page allocator (lowest free id first).

    Determinism matters: the scheduler invariant is that the same trace +
    seed produces identical per-request streams regardless of slot
    assignment order, and page ids feed the compiled steps' block tables.

    With ``ranks > 1`` (the DP slot-pool dimension) the pool is partitioned
    into per-rank regions: rank ``r`` owns global page ids
    ``[r*(num_pages+1), r*(num_pages+1) + num_pages)`` — each rank's region
    mirrors the single-rank device layout of ``num_pages`` real pages plus
    one trash row, so rank 0's ids (and thus block tables, and thus streams)
    are bit-identical to the ``ranks=1`` pool.  Allocation is per-rank
    (``alloc(n, rank=r)``); a slot's pages never cross ranks.  Per-rank
    trash rows below the last rank exist in the device layout but are
    unused — only the single *global* trash page is ever written.
    """

    def __init__(self, num_pages: int, page_size: int, ranks: int = 1):
        if num_pages < 1 or page_size < 1:
            raise ValueError(f"need >= 1 page of >= 1 token, got "
                             f"{num_pages} x {page_size}")
        if ranks < 1:
            raise ValueError(f"need >= 1 rank, got {ranks}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.ranks = ranks
        self._stride = num_pages + 1
        # Per-rank free lists, each kept sorted ascending (global ids).
        self._free = [list(range(r * self._stride, r * self._stride + num_pages))
                      for r in range(ranks)]
        self.high_water = 0

    @property
    def trash_page(self) -> int:
        """Id of the write-sink page: the LAST device row across all ranks
        (``num_pages`` when ranks == 1, matching the legacy layout)."""
        return self.ranks * self._stride - 1

    @property
    def total_pages(self) -> int:
        """Aggregate real (non-trash) pages across all ranks."""
        return self.ranks * self.num_pages

    @property
    def in_use(self) -> int:
        return self.total_pages - self.free_pages

    @property
    def free_pages(self) -> int:
        return sum(len(f) for f in self._free)

    def _rank_of(self, page: int) -> int:
        rank = page // self._stride
        if not (0 <= rank < self.ranks) or page % self._stride >= self.num_pages:
            raise ValueError(f"free of out-of-range page {page}")
        return rank

    def alloc(self, n: int, rank: int = 0) -> Optional[list[int]]:
        """Take the n lowest free page ids of ``rank``, or None (nothing
        taken) if that rank's region can't satisfy the request."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if not (0 <= rank < self.ranks):
            raise ValueError(f"alloc on rank {rank} of {self.ranks}")
        free = self._free[rank]
        if n > len(free):
            return None
        pages, self._free[rank] = free[:n], free[n:]
        self.high_water = max(self.high_water, self.in_use)
        return pages

    def free(self, pages: list[int]) -> None:
        if len(set(pages)) != len(pages):
            raise ValueError(f"duplicate page ids in free: {pages}")
        for p in pages:
            rank = self._rank_of(p)
            if p in self._free[rank]:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._free[self._rank_of(p)].append(p)
        for f in self._free:
            f.sort()

    def free_lists(self) -> list[list[int]]:
        """Snapshot of the per-rank free lists (copies, for checkpointing)."""
        return [list(f) for f in self._free]

    def restore_free(self, lists: list[list[int]]) -> None:
        """Restore free lists from a snapshot (inverse of ``free_lists``)."""
        if len(lists) != self.ranks:
            raise ValueError(f"snapshot has {len(lists)} rank free-lists, "
                             f"pool has {self.ranks}")
        self._free = [sorted(int(p) for p in f) for f in lists]


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold n_tokens positions (at least one)."""
    return max(1, -(-n_tokens // page_size))


def ring_pages(window: int, chunk: int, page_size: int) -> int:
    """Pages of one slot's window ring: position p lives at entry ``p mod
    (ring_pages * page_size)``.  A prefill chunk of ``chunk`` rows reads
    keys ``window - 1`` positions before its first row, and its own rows
    (merged by position, written after the layer scan): ``window + chunk -
    1`` positions, each still in the ring when the chunk reads it.  A decode
    step reads ``window`` positions, fewer."""
    return pages_for(window + chunk - 1, page_size)
