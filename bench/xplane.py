"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The harness marks the traced sub-window with a host span ``bench_window``
and each phase of its loop with spans of their own (``feed``, ``tick``,
``harvest``).  From the device planes it takes the op events (line
``XLA Ops``) and the program events (line ``XLA Modules``), all on the
host's clock:

* busy time: the union of op intervals inside the window, per device
  (a device plane is one with op events);
* op time by op name (the compiled program's instruction name, such as
  ``fusion.12`` or ``tdvmm_fused_kernel.3``: a TPU trace names an op event
  by its whole HLO text, ``%fusion.12 = bf16[...] fusion(...)``, and the
  name is what stands before `` = ``);
* program executions: count and device time per program, so that a step
  program can be told apart by how often it ran, not by its name.

Idle gaps, labelled by the host spans around them, and time by step scope
come from ``scopes.py``.
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW_SPAN = "bench_window"


def find_trace(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


HOST_SPANS = ("feed", "tick", "harvest")
TEXT_CHARS = 160


def op_name(text: str) -> str:
    """The instruction name of an op event named by its HLO text."""
    return text[1:].split(" = ", 1)[0] if text.startswith("%") else text


def load(path: str) -> dict:
    """The window span and per-device op and program events, as plain
    tuples (name, start_ns, end_ns); ``op_text``: the first ``TEXT_CHARS``
    characters of each op's HLO text."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: list = []
    devices: dict = {}
    text: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        host.append((ev.name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/device:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if kind is None:
                    continue
                for ev in line.events:
                    name = ev.name
                    if kind == "ops":
                        name = op_name(ev.name)
                        text.setdefault(name, ev.name.lstrip("%")[:TEXT_CHARS])
                    dev[kind].append((name, ev.start_ns, ev.end_ns))
    return {"host": host, "devices": devices, "op_text": text}


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window(host: list) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no host span {WINDOW_SPAN!r}")
    return spans[-1]


def reduce(trace: dict) -> dict:
    """The numbers of one traced window (seconds), averaged over devices."""
    lo, hi = window(trace["host"])
    # A TPU trace also holds device planes on which no op ever runs; such a
    # plane is no chip of the run, and counted as one it would halve every
    # average.
    devs = {k: v for k, v in trace["devices"].items() if v["ops"]}
    if not devs:
        raise ValueError("trace has no device plane with ops")
    busy, ops, modules = 0.0, collections.Counter(), {}
    op_count = collections.Counter()
    for dev in devs.values():
        merged = merge([(s, e) for _, s, e in dev["ops"]], lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, e in dev["ops"]:
            if s >= lo and e <= hi:
                ops[name] += e - s
                op_count[name] += 1
        for name, s, e in dev["modules"]:
            if s >= lo and e <= hi:
                c = modules.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += e - s
    n = len(devs)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n,
        "ops": {k: [op_count[k], v * 1e-9 / n] for k, v in ops.items()},
        "modules": {k: [c, t * 1e-9 / n] for k, (c, t) in modules.items()},
        "top_ops": [[trace.get("op_text", {}).get(k, k), v * 1e-9 / n]
                    for k, v in ops.most_common(10)],
    }


def kernel_time(ops: dict, kernel: str, launches: int) -> float:
    """Device seconds of one kernel's events in a reduced trace: the ops
    named ``<kernel>.<n>`` (the compiled programs name each launch site
    after the kernel's entry point).  Their number has to be ``launches``,
    the launches the window's steps make; a trace that names the kernel
    otherwise, or counts other work under its name, is an error, not a
    missing reading."""
    runs, spent = 0, 0.0
    for name, (n, sec) in ops.items():
        if re.sub(r"\.\d+$", "", name) == kernel:
            runs += n
            spent += sec
    if runs != launches:
        raise ValueError(f"{runs} {kernel} events in the trace, {launches} "
                         "launches expected")
    return spent


def program(modules: dict, runs: int, other=None) -> tuple[str, int, float] | None:
    """The program that ran exactly ``runs`` times in the window and took
    the most device time among those: a step program, told apart from the
    small per-step programs around it (argmax, isnan) by its time.  ``other``
    (a result of this function) is never chosen again."""
    if runs <= 0:
        return None
    skip = other[0] if other else None
    hits = [(t, name, c) for name, (c, t) in modules.items()
            if c == runs and name != skip]
    if not hits:
        return None
    t, name, c = max(hits)
    return name, c, t
