"""Pipeline parallelism over the pod axis (GPipe-style, inference pipeline).

The paper's section 3.1 chains VMM stages so that phase II of stage l IS
phase I of stage l+1, with a new sample admitted every period (Fig. 2d).  At
pod scale the same schedule maps onto the `pod` mesh axis: each pod owns a
contiguous half of the layer stack; microbatches stream through, and the
stage boundary is one collective_permute hop per microbatch — the only
cross-pod traffic (cheap on data-center interconnect vs FSDP gathers).

Implementation: `jax.shard_map` with `axis_names={'pod'}` — the pod axis is
manual (explicit permutes), while `data`/`model` stay AUTO, so the FSDP+TP
sharding of each stage's layers is still GSPMD's job inside the stage.

Layer stacks are (n_layers, ...) pytrees; we reshape to (n_stages,
layers_per_stage, ...) and shard dim 0 over `pod`.  Every pod executes the
same scanned-stage program on ITS slice; tokens enter at stage 0, exit at
stage n-1, and the GPipe schedule runs n_micro + n_stages - 1 ticks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import common, transformer


def stage_split_params(block_params: dict, n_stages: int):
    """(L, ...) stacked seg params -> (n_stages, L/n_stages, ...)."""
    def split(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])
    return jax.tree.map(split, block_params)


def pp_forward(params, batch_tokens, cfg: ModelConfig, mesh, n_micro: int = 8):
    """Pipelined forward (logits) for a homogeneous dense stack.

    params: full model params (model.init_params layout, single 'seg0').
    batch_tokens: (B, S) int32, B % n_micro == 0.
    """
    n_stages = mesh.shape["pod"]
    staged = stage_split_params(params["blocks"]["seg0"], n_stages)

    def body(p_stage, x):
        """Run this pod's layers on a microbatch of hidden states."""
        def layer(h, lp):
            h2, _, _ = transformer.attn_ffn_block(
                lp, h, cfg, "train", None,
                jnp.broadcast_to(jnp.arange(h.shape[1], dtype=jnp.int32),
                                 h.shape[:2]))
            return h2, None
        x, _ = jax.lax.scan(layer, x, p_stage)
        return x

    def pipelined(staged_local, x_mb, stage_id):
        """staged_local: (1, L/stages, ...) this pod's layers;
        x_mb: (n_micro, mb, S, d) embedded microbatches (same on every pod —
        only stage 0's compute consumes them);
        stage_id: (1,) this pod's stage index, passed as pod-sharded data
        because lax.axis_index lowers to PartitionId, which GSPMD rejects
        inside a partially-auto shard_map on jax 0.4.x."""
        stage_params = jax.tree.map(lambda a: a[0], staged_local)
        idx = stage_id[0]
        n_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            buf = carry                       # (mb, S, d) current stage input
            # stage 0 ingests microbatch t (older stages work on forwarded data)
            fresh = x_mb[jnp.minimum(t, n_micro - 1)]
            buf = jnp.where(idx == 0, jnp.where(t < n_micro, fresh, buf), buf)
            out = body(stage_params, buf)
            # forward to the next stage (last stage's permute wraps, ignored)
            nxt = jax.lax.ppermute(
                out, "pod", [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # emit: only the LAST stage's output at valid ticks is real
            emit = jnp.where(idx == n_stages - 1, out, jnp.zeros_like(out))
            return nxt, emit

        _, emitted = jax.lax.scan(tick, jnp.zeros_like(x_mb[0]), jnp.arange(n_ticks))
        # microbatch m exits the last stage at tick m + n_stages - 1
        outs = emitted[n_stages - 1:]
        # broadcast last stage's result to every pod so the head is replicated
        outs = jax.lax.psum(outs, "pod") / 1.0  # zeros elsewhere -> identity
        return outs

    # embed outside the pipeline (replicated over pod)
    x = params["embed"]["table"][batch_tokens]
    b, s, d = x.shape
    assert b % n_micro == 0
    x_mb = x.reshape(n_micro, b // n_micro, s, d)

    staged_specs = jax.tree.map(lambda _: P("pod"), staged)
    outs = jax.shard_map(
        pipelined, mesh=mesh,
        in_specs=(staged_specs, P(), P("pod")),
        out_specs=P(),
        axis_names={"pod"},
        check_vma=False,
    )(staged, x_mb, jnp.arange(n_stages, dtype=jnp.int32))

    h = outs.reshape(b, s, d)
    h = common.rmsnorm(params["ln_f"], h, cfg.norm_eps)
    if cfg.tie_embeddings:
        return h @ params["embed"]["table"].T
    return common.dense(params["head"], h, cfg.site_tdvmm("head"))
