"""Launches and model work of an AFMoE model, from its published shapes and
the chip's share of its experts.

* A step of M rows: ``attn.qkv`` is one launch over the column concat of
  q, k, v and the gate, each rounded up to the 128-wide lane; ``attn.out``
  one; a dense layer's gated ``ffn.in`` two and ``ffn.out`` one; an MoE
  layer's held experts one batched launch per projection (G = the experts
  held, M = the program's dispatch capacity, which is the step's M rows:
  dropless, a row picks an expert at most once), its shared expert one
  launch per projection of M rows; the untied head one launch on the
  step's head rows.
* Model work per processed token: every dense analog site's ``2 K N``
  in int8, and of the expert layer the shared expert and the routed rows
  that land on the experts held here, ``top_k * held / E`` a token on
  average; attention ``4 H hd`` per key in bf16, ``p + 1`` keys at
  position p on a full layer and ``min(p + 1, W)`` on a window layer.
"""
from __future__ import annotations

LANE = 128


def _lane(n: int) -> int:
    return -(-n // LANE) * LANE


def shapes(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:layers]
    pad = cfg["vocab_pad_multiple"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "hd": cfg["head_dim"], "kv": cfg["num_key_value_heads"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "layers": layers, "dense": cfg["num_dense_layers"],
            "sparse": layers - cfg["num_dense_layers"],
            "window_layers": sum(k == "sliding_attention" for k in kinds),
            "window": cfg["sliding_window"], "held": cfg["num_experts"],
            "experts": cfg["published"]["num_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "shared": cfg["num_shared_experts"], "vocab": cfg["vocab_size"],
            "vp": -(-cfg["vocab_size"] // pad) * pad}


def step_launches(cfg: dict, m: int, head_rows: int) -> list[tuple[str, int, int, int, int, int]]:
    """(site, G, M, K, N, launches) of one step of M rows whose head runs
    on ``head_rows`` rows (the decode step: all M; a prefill chunk: 1)."""
    s = shapes(cfg)
    d, q, kv = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    layers, dense, sparse = s["layers"], s["dense"], s["sparse"]
    return [("attn.qkv", 1, m, d, 2 * _lane(q) + 2 * _lane(kv), layers),
            ("attn.out", 1, m, q, d, layers),
            ("ffn.in", 1, m, d, s["f"], 2 * dense),
            ("ffn.out", 1, m, s["f"], d, dense),
            ("moe.expert.in", s["held"], m, d, s["fe"], 2 * sparse),
            ("moe.expert.out", s["held"], m, s["fe"], d, sparse),
            ("moe.shared.in", s["shared"], m, d, s["fe"], 2 * sparse),
            ("moe.shared.out", s["shared"], m, s["fe"], d, sparse),
            ("head", 1, head_rows, d, s["vp"], 1)]


def model_work(cfg: dict, tokens: int, heads: int,
               ranges: list[tuple[int, int]]) -> tuple[float, float]:
    """(int8 ops, bf16 flops) of ``tokens`` processed positions, ``heads``
    rows through the head, and attention over each request's processed
    positions ``start <= p < end``."""
    s = shapes(cfg)
    d, q, kv, fe = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"], s["fe"]
    attn = 2.0 * (d * (2 * q + 2 * kv) + q * d)
    experts = s["top_k"] * s["held"] / s["experts"] + s["shared"]
    per_token = (attn * s["layers"] + 6.0 * d * s["f"] * s["dense"]
                 + 6.0 * d * fe * experts * s["sparse"])
    full_keys = sum((e * (e + 1) - b * (b + 1)) // 2 for b, e in ranges)
    w = s["window"]
    window_keys = sum(_window_keys(b, e, w) for b, e in ranges)
    full_layers = s["layers"] - s["window_layers"]
    bf16 = 4.0 * s["h"] * s["hd"] * (full_layers * full_keys
                                     + s["window_layers"] * window_keys)
    return tokens * per_token + heads * 2.0 * d * s["vocab"], bf16


def _window_keys(b: int, e: int, w: int) -> int:
    """Keys read at positions b <= p < e: ``min(p + 1, w)`` each."""
    ramp = max(0, min(e, w) - b)          # positions p < w read p + 1 keys
    return (b + ramp) * (b + ramp + 1) // 2 - b * (b + 1) // 2 + w * (e - b - ramp)
