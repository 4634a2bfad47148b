"""Launches and model work of a Qwen2 model, from its published shapes.

* A TD-VMM launch of an analog site on an M-row step is one (M, K, N)
  tile (G = 1).  ``attn.qkv`` is one launch over the column concat of q, k
  and v, each rounded up to the 128-wide lane; the gated ``ffn.in`` is two
  launches; an untied head is one launch on the step's head rows.
* Model work per processed token: every analog site's ``2 K N`` (logical
  widths) in int8; a tied (digital) head's ``2 d V`` in bf16; attention
  ``4 H hd (p + 1)`` per layer at causal position ``p``, in bf16.
"""
from __future__ import annotations

LANE = 128


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


def step_launches(cfg: dict, m: int, head_rows: int) -> list[tuple[str, int, int, int, int, int]]:
    """(site, G, M, K, N, launches) of one step of M rows whose head runs
    on ``head_rows`` rows (the decode step: all M; a prefill chunk: 1)."""
    s = shapes(cfg)
    out = [(site, 1, m, k, n, c * s["layers"]) for site, k, n, c in layer_launches(cfg)]
    if not s["tied"]:
        out.append(("head", 1, head_rows, s["d"], s["vp"], 1))
    return out


def model_work(cfg: dict, tokens: int, heads: int,
               ranges: list[tuple[int, int]]) -> tuple[float, float]:
    """(int8 ops, bf16 flops) of ``tokens`` processed positions, ``heads``
    rows through the head, and causal attention over each request's
    processed positions ``start <= p < end`` (``p + 1`` keys each)."""
    s = shapes(cfg)
    d, q, f = s["d"], s["h"] * s["hd"], s["f"]
    site = 2.0 * (d * (q + 2 * s["kv"] * s["hd"]) + q * d + 2 * d * f + f * d)
    position_sum = sum((e * (e + 1) - b * (b + 1)) // 2 for b, e in ranges)
    int8 = tokens * site * s["layers"]
    bf16 = 4.0 * s["h"] * s["hd"] * s["layers"] * position_sum
    head = heads * 2.0 * s["d"] * s["vocab"]
    if s["tied"]:
        bf16 += head
    else:
        int8 += head
    return int8, bf16
