"""The benchmark's one dependence on the engine's internals.

``runtime.engine.Engine`` has no public way to add a request to a running
engine, nor to read its counters between ticks.  ``Driver`` does both, over
the public ``start`` / ``tick``, and is the only code of the benchmark that
touches ``Engine._st``.  A public ``Engine.submit()`` would replace it.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass
class Counts:
    steps: int
    prefill_steps: int
    decode_steps: int
    prompt_tokens: int
    generated_tokens: int
    active_slot_steps: float
    nan_steps: int
    failed: int
    evictions: int

    def minus(self, other: "Counts") -> "Counts":
        return Counts(**{f.name: getattr(self, f.name) - getattr(other, f.name)
                         for f in dataclasses.fields(self)})


class Driver:
    def __init__(self, engine):
        self.engine = engine

    def start(self) -> None:
        """A fresh run with an empty queue (new KV pool)."""
        self.engine._st = None
        self.engine.start([])

    @property
    def _st(self):
        return self.engine._st

    def add(self, requests) -> None:
        """Queue requests, due now: each arrives at the current engine step,
        so the next tick may admit it."""
        from repro.runtime.scheduler import RequestRecord
        st = self._st
        reqs = [dataclasses.replace(r, arrival_step=st.steps) for r in requests]
        st.requests.extend(reqs)
        for r in reqs:
            st.records[r.rid] = RequestRecord(r)
        st.sched.add(reqs)

    def tick(self) -> bool:
        return self.engine.tick()

    def pending(self) -> int:
        return len(self._st.sched.pending)

    def occupied(self) -> list:
        """(rid, tokens so far, positions in the KV cache) of every occupied
        slot."""
        return [(s.record.request.rid, len(s.record.tokens), s.pos)
                for s in self._st.sched.occupied()]

    def record(self, rid: int):
        """(tokens so far, finish reason or None) of one request."""
        rec = self._st.records[rid]
        return rec.tokens, rec.finish_reason

    def counts(self) -> Counts:
        st = self._st
        return Counts(st.steps, st.prefill_steps, st.decode_steps,
                      st.prompt_tokens, st.generated_tokens,
                      float(sum(st.util_samples)) * self.engine.total_slots,
                      st.nan_steps, st.failed, st.evictions)

    def sync(self) -> None:
        jax.block_until_ready(self._st.caches)

    def compiled_steps(self) -> int:
        return self.engine.compiled_steps()
