"""Operations and bytes of the served model, from the configuration alone.

This is the yardstick of the roofline and ``mfu`` metrics: the work the
model needs, counted from its published shapes, independent of how the
program pads, fuses or schedules it.  The architecture's package
(``archs/<name>/work.py``) lists a step's launches and the model's work;
this module prices them.

* A TD-VMM launch computes G tiles of (M, K, N): int8 operations
  ``2 G M K N``; bytes ``G (M K + K N + 4 M N)`` (input codes, weight
  codes, f32 output).  Its least time on a chip is the larger of ops over
  the int8 peak and bytes over the HBM bandwidth.
* Model work: the architecture's int8 operations at the int8 peak and its
  bf16 operations at the bf16 peak.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import archs


def peaks(device_kind: str) -> dict:
    """The peak table row of a device; an unknown device is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} "
                         f"in bench/peaks.json") from None


def launch_cost(g: int, m: int, k: int, n: int, pk: dict) -> tuple[float, float, str]:
    """(least seconds, ops per byte, the bound that sets it) of one launch."""
    ops = 2.0 * g * m * k * n
    nbytes = float(g * (m * k + k * n + 4 * m * n))
    t_ops, t_bytes = ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), ops / nbytes, "ops" if t_ops >= t_bytes else "bytes"


def window_launches(cfg: dict, decode_steps: int, slots: int,
                    prefill_steps: int, chunk: int):
    """(site, G, M, K, N, launches) of every TD-VMM launch that
    ``decode_steps`` decode steps and ``prefill_steps`` prefill chunks make."""
    step = archs.find(cfg).work.step_launches
    return [(site, g, mm, k, n, steps * c)
            for steps, m, head_rows in ((decode_steps, slots, slots),
                                        (prefill_steps, chunk, 1))
            for site, g, mm, k, n, c in step(cfg, m, head_rows)]


def least_kernel_seconds(cfg: dict, pk: dict, decode_steps: int, slots: int,
                         prefill_steps: int, chunk: int) -> float:
    """Least time of every TD-VMM launch of those steps."""
    return sum(c * launch_cost(g, m, k, n, pk)[0] for _, g, m, k, n, c in
               window_launches(cfg, decode_steps, slots, prefill_steps, chunk))


def kernel_launches(cfg: dict, decode_steps: int, slots: int,
                    prefill_steps: int, chunk: int) -> int:
    """How many TD-VMM launches those steps make."""
    return sum(c for *_, c in
               window_launches(cfg, decode_steps, slots, prefill_steps, chunk))


def model_seconds(cfg: dict, pk: dict, tokens: int, heads: int,
                  ranges: list[tuple[int, int]]) -> float:
    """The model's work at peak (``archs/<name>/work.py`` ``model_work``):
    ``tokens`` processed positions, ``heads`` rows through the head, the
    processed ``(start, end)`` position range of each request."""
    int8, bf16 = archs.find(cfg).work.model_work(cfg, tokens, heads, ranges)
    return int8 / pk["int8_ops_per_s"] + bf16 / pk["bf16_flops_per_s"]
