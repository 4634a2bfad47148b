"""Traffic made from a mix file (``traffic/<mix>.json``) and a seed.

A mix is an offline backlog: the harness keeps the engine's queue full from
this stream.  The stream is a sequence of cycles.  Each cycle holds the same
``pool`` requests: their prompt and output lengths are the lognormal
distribution's quantiles at (i + 1/2) / pool (``median``, ``sigma``,
rounded and clipped to ``[min, max]``), paired in a fixed shuffle, in an
order of their own in each cycle.  Those orders are fixed too: the run's
seed fills the prompts with token ids, and nothing else.  A window of a
few tens of seconds holds less than a cycle of a slow cell, and the
requests that finish in it set how many prompt tokens it absorbs, so
seeds that ordered the cycles differently would do different work.  With
the order fixed every seed does the same work in the same order, and a
run's spread is the system's, not the sample's.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

PAIRING_SEED = 0x5EED
ORDER_SEED = 0x7AFF1C


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """The n quantiles at (i + 1/2) / n, ascending."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of the stream: its lengths."""
    prompt_len: int
    output_len: int


class Stream:
    """The seeded request stream of one mix."""

    def __init__(self, mix: dict, seed: int):
        if mix["loop"] != "offline":
            raise ValueError(f"bench: mix loop {mix['loop']!r}: only an "
                             "offline backlog is implemented")
        self.n = mix["pool"]
        self.prompt_lens = lognormal_lengths(mix["prompt"], self.n)
        self.output_lens = lognormal_lengths(mix["output"], self.n)[
            np.random.default_rng(PAIRING_SEED).permutation(self.n)]
        self._order = np.random.default_rng(ORDER_SEED)
        self._cycles: list[np.ndarray] = []
        self._tokens = np.random.default_rng([int(seed) % (1 << 64), 0x70C5])

    def item(self, i: int) -> Item:
        """The i-th request of the stream."""
        c, j = divmod(i, self.n)
        while len(self._cycles) <= c:
            self._cycles.append(self._order.permutation(self.n))
        k = self._cycles[c][j]
        return Item(int(self.prompt_lens[k]), int(self.output_lens[k]))

    def prompt(self, item: Item, vocab: int) -> tuple[int, ...]:
        """Token ids of a prompt, drawn in stream order."""
        return tuple(int(t) for t in self._tokens.integers(0, vocab, item.prompt_len))
