"""jit-able train step factory and the train state it carries.

train_step supports gradient-accumulation microbatching (scan over G
microbatches, fp32 grad accumulators) — the memory/throughput lever for the
big configs — and donates the train state.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import model
from repro.optim.optimizer import Optimizer, OptState


class TrainState(NamedTuple):
    params: Any
    opt: OptState


def init_train_state(key, cfg: ModelConfig, optimizer: Optimizer) -> TrainState:
    params = model.init_params(key, cfg)
    return TrainState(params=params, opt=optimizer.init(params))


def grad_accum_steps(run: RunConfig, dp_size: int) -> int:
    """How many microbatches per step."""
    shape = run.shape
    if shape.kind != "train":
        return 1
    per_shard = max(shape.global_batch // max(dp_size, 1), 1)
    mb = shape.microbatch_per_shard or _auto_microbatch(run.model, shape.seq_len)
    mb = min(mb, per_shard)
    return max(per_shard // mb, 1)


def _auto_microbatch(cfg: ModelConfig, seq_len: int) -> int:
    """Target ~8k tokens per shard per microbatch."""
    return max(8192 // seq_len, 1)


def make_train_step(cfg: ModelConfig, run: RunConfig, optimizer: Optimizer,
                    accum: int = 1):
    def loss(params, batch):
        return model.loss_fn(params, batch, cfg)

    def train_step(state: TrainState, batch: dict):
        params = state.params
        if accum <= 1:
            (_, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params, batch)
        else:
            mb_batch = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"loss": jnp.zeros((), jnp.float32),
                  "lb_loss": jnp.zeros((), jnp.float32),
                  "z_loss": jnp.zeros((), jnp.float32),
                  "tokens": jnp.zeros((), jnp.float32)}

            def mb_step(carry, mb):
                gsum, msum = carry
                (_, m), g = jax.value_and_grad(loss, has_aux=True)(params, mb)
                gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
                msum = {k: msum[k] + m[k] for k in msum}
                return (gsum, msum), None

            (grads, msum), _ = jax.lax.scan(mb_step, (g0, m0), mb_batch)
            grads = jax.tree.map(lambda g: g / accum, grads)
            metrics = {k: v / accum for k, v in msum.items()}
            metrics["tokens"] = msum["tokens"]

        new_params, new_opt, opt_metrics = optimizer.update(grads, state.opt, params)
        metrics.update(opt_metrics)
        metrics["step"] = state.opt.step
        return TrainState(new_params, new_opt), metrics

    return train_step

