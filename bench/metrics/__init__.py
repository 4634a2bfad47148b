"""Per-layer metric readers, one file per metric, found by the metric's name.

``metrics/<name>.py`` defines ``reduce(record) -> float | None``; ``None``
(nothing to read in this run) leaves the metric out of the result line.
The record a traced run hands them is built in ``harness.run``: the
reduced trace (``trace``), the engine's counts over the traced window
(``counts``: every numeric counter of its ``RunState`` and
``active_slot_steps``), the step programs told apart by their run counts
(``decode_program``, ``prefill_program``: name, runs, device seconds) and
the name of the one with the most device time (``main_program``), self
milliseconds per run of each program by step scope (``scopes``: program ->
scope name in ``runtime/trace.STEP_SCOPES``, or None, -> ms), the least
kernel time, the number of kernel launches and the model's work at peak
over that window (``least_kernel_s``, ``kernel_launches``, ``model_s``),
and the whole window's host-clock numbers (``host``).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path


def read(name: str, record: dict):
    path = Path(__file__).parent / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.reduce(record)
    return None if value is None else float(value)
