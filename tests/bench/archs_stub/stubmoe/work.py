"""The stub's launches and model work.

A dense layer makes the four dense launches of one (M, K, N) tile.  A
sparse layer routes each of a step's M rows to ``top_k`` of E experts; its
batched expert launch computes E tiles of ceil(M top_k / E) rows at once
(G = E), its shared expert one tile of M rows.  Attention at position p
reads ``min(p + 1, sliding_window)`` keys."""
from __future__ import annotations


def step_launches(cfg: dict, m: int, head_rows: int):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    e, f, ff = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["intermediate_size"]
    rows = -(-m * cfg["num_experts_per_tok"] // e)
    vp = -(-cfg["vocab_size"] // cfg["vocab_pad_multiple"]) * cfg["vocab_pad_multiple"]
    layers = dense + sparse
    return [("attn.qkv", 1, m, d, q + 2 * kv, layers),
            ("attn.out", 1, m, q, d, layers),
            ("ffn.in", 1, m, d, ff, 2 * dense),
            ("ffn.out", 1, m, ff, d, dense),
            ("moe.expert.in", e, rows, d, f, 2 * sparse),
            ("moe.expert.out", e, rows, f, d, sparse),
            ("moe.shared.in", 1, m, d, f, 2 * sparse),
            ("moe.shared.out", 1, m, f, d, sparse),
            ("head", 1, head_rows, d, vp, 1)]


def model_work(cfg: dict, tokens: int, heads: int, ranges):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    f, ff, w = cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["sliding_window"]
    attn = 2 * (d * (h + 2 * kv) * hd + h * hd * d)
    per_token = ((attn + 6 * d * ff) * dense
                 + (attn + 6 * d * f * (cfg["num_experts_per_tok"] + cfg["num_shared_experts"]))
                 * sparse)
    keys = sum(min(p + 1, w) for b, e in ranges for p in range(b, e))
    return (tokens * per_token + heads * 2 * d * cfg["vocab_size"],
            4 * h * hd * (dense + sparse) * keys)
