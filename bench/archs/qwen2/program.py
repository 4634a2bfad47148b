"""The program's configuration for a Qwen2 configuration file."""
from __future__ import annotations


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the repo's
    architecture entry with the file's sizes, every listed analog site on."""
    from repro.configs import TDVMMPlan, get_config, tdvmm_rule
    td = cfg["tdvmm"]
    return get_config(
        cfg["arch"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], vocab_pad_multiple=cfg["vocab_pad_multiple"],
        tdvmm_plan=TDVMMPlan(rules=(tdvmm_rule(
            td["sites"], enabled=True, backend=td["backend"], bits=td["bits"],
            weight_bits=td["weight_bits"]),)))
