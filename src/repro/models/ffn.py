"""Feed-forward blocks: gated (SiLU-GLU) and non-gated (squared-ReLU / GELU)."""
from __future__ import annotations

import jax

from repro.configs.base import ModelConfig
from repro.models import common


def init(key, cfg: ModelConfig, d_ff: int | None = None, dtype=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.act == "silu_glu":
        return {
            "w_gate": common.dense_init(k1, d, f, dtype),
            "w_up": common.dense_init(k2, d, f, dtype),
            "w_down": common.dense_init(k3, f, d, dtype),
        }
    return {
        "w_up": common.dense_init(k1, d, f, dtype),
        "w_down": common.dense_init(k2, f, d, dtype),
    }


def apply(params, x: jax.Array, cfg: ModelConfig, key=None) -> jax.Array:
    td_in = cfg.site_tdvmm("ffn.in")
    if "w_gate" in params:
        h = common.activation("silu", common.dense(params["w_gate"], x, td_in, key))
        h = h * common.dense(params["w_up"], x, td_in, key)
    else:
        h = common.activation(cfg.act, common.dense(params["w_up"], x, td_in, key))
    return common.dense(params["w_down"], h, cfg.site_tdvmm("ffn.out"), key)
