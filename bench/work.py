"""Operations and bytes of the served model, from the configuration alone.

This is the yardstick of the roofline and ``mfu`` metrics: the work the
model needs, counted from its published shapes, independent of how the
program pads, fuses or schedules it.

* A TD-VMM launch of an analog site on an M-row step is (M, K, N): int8
  operations ``2 M K N``; bytes ``M K`` (input codes) + ``K N`` (weight
  codes) + ``4 M N`` (f32 output).  ``attn.qkv`` is one launch over the
  column concat of q, k and v, each rounded up to the 128-wide lane; the
  gated ``ffn.in`` is two launches.  Its least time on a chip is the larger
  of ops over the int8 peak and bytes over the HBM bandwidth.
* Model work per processed token: every analog site's ``2 K N`` (logical
  widths) at the int8 peak; a tied (digital) head's ``2 d V`` at the bf16
  peak; attention ``4 H hd (p + 1)`` per layer at causal position ``p``, at
  the bf16 peak.
"""
from __future__ import annotations

import json
from pathlib import Path

LANE = 128


def peaks(device_kind: str) -> dict:
    """The peak table row of a device; an unknown device is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} "
                         f"in bench/peaks.json") from None


def _lane(n: int) -> int:
    return -(-n // LANE) * LANE


def shapes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    kv = cfg["num_key_value_heads"]
    pad = cfg["vocab_pad_multiple"]
    return {"d": d, "h": h, "hd": hd, "kv": kv, "f": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "vp": -(-cfg["vocab_size"] // pad) * pad,
            "tied": bool(cfg["tie_word_embeddings"])}


def layer_launches(cfg: dict) -> list[tuple[str, int, int, int]]:
    """(site, K, N, launches) of one layer's analog sites."""
    s = shapes(cfg)
    d, q, kv = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    return [("attn.qkv", d, _lane(q) + 2 * _lane(kv), 1),
            ("attn.out", q, d, 1),
            ("ffn.in", d, s["f"], 2),
            ("ffn.out", s["f"], d, 1)]


def step_launches(cfg: dict, m: int, head_rows: int) -> list[tuple[str, int, int, int, int]]:
    """(site, M, K, N, launches) of one step of M rows whose head runs on
    ``head_rows`` rows (the decode step: all M; a prefill chunk: 1)."""
    s = shapes(cfg)
    out = [(site, m, k, n, c * s["layers"]) for site, k, n, c in layer_launches(cfg)]
    if not s["tied"]:
        out.append(("head", head_rows, s["d"], s["vp"], 1))
    return out


def launch_cost(m: int, k: int, n: int, pk: dict) -> tuple[float, float, str]:
    """(least seconds, ops per byte, the bound that sets it) of one launch."""
    ops = 2.0 * m * k * n
    nbytes = float(m * k + k * n + 4 * m * n)
    t_ops, t_bytes = ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), ops / nbytes, "ops" if t_ops >= t_bytes else "bytes"


def window_launches(cfg: dict, decode_steps: int, slots: int,
                    prefill_steps: int, chunk: int):
    """(site, M, K, N, launches) of every TD-VMM launch that ``decode_steps``
    decode steps and ``prefill_steps`` prefill chunks make."""
    return [(site, mm, k, n, steps * c)
            for steps, m, head_rows in ((decode_steps, slots, slots),
                                        (prefill_steps, chunk, 1))
            for site, mm, k, n, c in step_launches(cfg, m, head_rows)]


def least_kernel_seconds(cfg: dict, pk: dict, decode_steps: int, slots: int,
                         prefill_steps: int, chunk: int) -> float:
    """Least time of every TD-VMM launch of those steps."""
    return sum(c * launch_cost(m, k, n, pk)[0] for _, m, k, n, c in
               window_launches(cfg, decode_steps, slots, prefill_steps, chunk))


def kernel_launches(cfg: dict, decode_steps: int, slots: int,
                    prefill_steps: int, chunk: int) -> int:
    """How many TD-VMM launches those steps make."""
    return sum(c for *_, c in
               window_launches(cfg, decode_steps, slots, prefill_steps, chunk))


def model_seconds(cfg: dict, pk: dict, tokens: int, heads: int,
                  position_sum: int) -> float:
    """The model's matmul work at peak: ``tokens`` processed positions,
    ``heads`` rows through the head, ``position_sum`` = sum over processed
    positions of (p + 1), each kind of work at its own peak."""
    s = shapes(cfg)
    d, q, f = s["d"], s["h"] * s["hd"], s["f"]
    site = 2.0 * (d * (q + 2 * s["kv"] * s["hd"]) + q * d + 2 * d * f + f * d)
    int8 = tokens * site * s["layers"]
    bf16 = 4.0 * s["h"] * s["hd"] * s["layers"] * position_sum
    head = heads * 2.0 * s["d"] * s["vocab"]
    if s["tied"]:
        bf16 += head
    else:
        int8 += head
    return int8 / pk["int8_ops_per_s"] + bf16 / pk["bf16_flops_per_s"]
