"""Shared model components: norms, rotary embeddings, initialized dense layers.

All modules are functional pytrees: ``init(key, ...) -> params`` and
``apply(params, x, ...) -> y``.  Every dense matmul goes through
``core.layers.td_matmul`` so any linear can execute in TD-VMM mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.layers import TDVMMLayerConfig, td_grouped_matmul, td_matmul


def resolve_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


def constrain_batch(x: jax.Array) -> jax.Array:
    """Anchor activations' batch dim to the DP axes (no-op without a mesh).

    Batch size 1 (long_500k) stays replicated — GSPMD can't split it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import meshctx

    mesh = meshctx.get_mesh()
    if mesh is None:
        return x
    dp = meshctx.dp_axes()
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    if x.shape[0] % n != 0:
        return x
    spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings (rotate-half convention)
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Dense (TD-VMM-aware)
# --------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, bias: bool = False,
               scale: float | None = None):
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params, x: jax.Array, td: TDVMMLayerConfig, key=None) -> jax.Array:
    y = td_matmul(x, params["w"], td, key)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def dense_group(param_group, x: jax.Array, td: TDVMMLayerConfig,
                key=None) -> tuple[jax.Array, ...]:
    """G same-input dense projections as ONE shared-input TD-VMM launch.

    The grouped sites (``attn.qkv``: wq/wk/wv, ``ssm.in_proj``:
    wz/wx/wB/wC/wdt) project the same activation through several matrices;
    this encodes x once and runs all G members as a single ragged column
    concat launch (``core.layers.td_grouped_matmul`` — each member padded
    only to the 128 lane, not to the widest member, so uneven GQA widths
    carry no padding overhead) instead of G ``dense`` calls.  Biases stay
    per-member digital adds."""
    ys = td_grouped_matmul(x, tuple(p["w"] for p in param_group), td, key)
    return tuple(
        y + p["b"].astype(y.dtype) if "b" in p else y
        for p, y in zip(param_group, ys))


def activation(name: str, x: jax.Array) -> jax.Array:
    if name == "sq_relu":
        r = jax.nn.relu(x)
        return r * r
    if name == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)
