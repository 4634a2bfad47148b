"""The program's MoE configuration for a stub configuration file."""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.configs import MoEConfig, TDVMMPlan, get_config, tdvmm_rule
    td = cfg["tdvmm"]
    return get_config(
        "mixtral-8x7b", n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], tie_embeddings=False,
        swa_window=cfg["sliding_window"], vocab_pad_multiple=cfg["vocab_pad_multiple"],
        moe=MoEConfig(n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
                      d_ff=cfg["moe_intermediate_size"],
                      n_shared_experts=cfg["num_shared_experts"],
                      first_k_dense=cfg["first_k_dense_replace"]),
        tdvmm_plan=TDVMMPlan(rules=(tdvmm_rule(
            td["sites"], enabled=True, backend=td["backend"], bits=td["bits"],
            weight_bits=td["weight_bits"]),)))
