"""The benchmark's one dependence on the engine's internals.

``runtime.engine.Engine`` has no public way to add a request to a running
engine, nor to read its counters between ticks, nor to hand out its
compiled step programs.  ``Driver`` does all three, over the public
``start`` / ``tick``, and is the only code of the benchmark that touches
``Engine._st`` and ``Engine._run_compiled``.  A public ``Engine.submit()``
would replace it.
"""
from __future__ import annotations

import dataclasses

import jax


class Counts(dict):
    """Every numeric counter of the engine's ``RunState`` at one moment, by
    name, and ``active_slot_steps`` (active slots summed over decode steps);
    read as attributes too.  A counter the engine adds reaches the readers
    with no change here."""

    def __getattr__(self, name: str):
        try:
            return self[name]
        except KeyError:
            raise AttributeError(name) from None

    def minus(self, other: "Counts") -> "Counts":
        return Counts({k: v - other[k] for k, v in self.items()})


class Driver:
    def __init__(self, engine):
        self.engine = engine
        self._steps: dict = {}
        run = engine._run_compiled

        def record(name, fn, *args, **kw):
            # Shapes, dtypes and weak types of each step program's first
            # call, for ``step_hlo``: lowered from these, a one-device step
            # is the program the call compiled (with the arrays' shardings
            # it would not be).
            if name not in self._steps:
                self._steps[name] = (fn, jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   weak_type=x.weak_type), args))
            return run(name, fn, *args, **kw)

        engine._run_compiled = record

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
        c = Counts({f.name: getattr(st, f.name) for f in dataclasses.fields(st)
                    if type(getattr(st, f.name)) in (int, float)})
        c["active_slot_steps"] = float(sum(st.util_samples)) * self.engine.total_slots
        return c

    def step_hlo(self) -> dict[str, str]:
        """Program name -> optimised HLO text of each step program the engine
        has run, lowered again for the arguments of its first call and
        compiled (found in the persistent compilation cache where that is
        on)."""
        out = {}
        for fn, args in self._steps.values():
            text = fn.lower(*args).compile().as_text()
            out[text.split(None, 2)[1].rstrip(",")] = text
        return out

    def sync(self) -> None:
        jax.block_until_ready(self._st.caches)

    def compiled_steps(self) -> int:
        return self.engine.compiled_steps()
