"""The program's configuration for an AFMoE configuration file."""
from __future__ import annotations


def model_config(cfg: dict):
    """The program's ModelConfig: the repo's architecture entry with the
    file's sizes, the held share of the experts (``num_experts`` of the
    published count, from ``expert_offset``), every listed analog site on."""
    import dataclasses

    from repro.configs import TDVMMPlan, get_config, tdvmm_rule
    td = cfg["tdvmm"]
    base = get_config(cfg["arch"])
    layers = cfg["num_hidden_layers"]
    return base.replace(
        n_layers=layers, d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), swa_window=cfg["sliding_window"],
        layer_types=tuple(cfg["layer_types"][:layers]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        embed_scale=bool(cfg["mup_enabled"]), dtype=cfg["torch_dtype"],
        vocab_pad_multiple=cfg["vocab_pad_multiple"],
        moe=dataclasses.replace(
            base.moe, n_experts=cfg["published"]["num_experts"],
            held=cfg["num_experts"], held_offset=cfg["expert_offset"],
            top_k=cfg["num_experts_per_tok"], d_ff=cfg["moe_intermediate_size"],
            n_shared_experts=cfg["num_shared_experts"],
            first_k_dense=cfg["num_dense_layers"],
            score_func=cfg["score_func"], route_scale=float(cfg["route_scale"])),
        tdvmm_plan=TDVMMPlan(rules=(tdvmm_rule(
            td["sites"], enabled=True, backend=td["backend"], bits=td["bits"],
            weight_bits=td["weight_bits"]),)))
