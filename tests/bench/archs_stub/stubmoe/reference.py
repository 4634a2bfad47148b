"""The stub's parameter layout (the test drives no reference model)."""
from __future__ import annotations


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    e, s, f = cfg["num_experts"], cfg["num_shared_experts"], cfg["moe_intermediate_size"]
    vp = -(-cfg["vocab_size"] // cfg["vocab_pad_multiple"]) * cfg["vocab_pad_multiple"]
    out = {"embed/table": (vp, d), "ln_f/scale": (d,), "head/w": (d, vp)}
    for seg, n in (("blocks/seg0/", dense), ("blocks/seg1/", sparse)):
        out.update({seg + "ln1/scale": (n, d), seg + "ln2/scale": (n, d),
                    seg + "attn/wq/w": (n, d, q), seg + "attn/wk/w": (n, d, kv),
                    seg + "attn/wv/w": (n, d, kv), seg + "attn/wo/w": (n, q, d)})
    ff = cfg["intermediate_size"]
    out.update({"blocks/seg0/ffn/w_gate/w": (dense, d, ff),
                "blocks/seg0/ffn/w_up/w": (dense, d, ff),
                "blocks/seg0/ffn/w_down/w": (dense, ff, d),
                "blocks/seg1/moe/router/w": (sparse, d, e)})
    for bank, g in (("experts", e), ("shared", s)):
        out.update({f"blocks/seg1/moe/{bank}/w_gate": (sparse, g, d, f),
                    f"blocks/seg1/moe/{bank}/w_up": (sparse, g, d, f),
                    f"blocks/seg1/moe/{bank}/w_down": (sparse, g, f, d)})
    return out
