"""Layer stacks: scanned homogeneous segments, remat, hybrid composition.

Stack layouts per family:
  dense/vlm/audio : n_layers x [attn + ffn]                    (one scanned seg)
  moe             : first_k_dense x [attn + ffn] + rest x [attn + moe]
  layer_types set : one segment per run of layers of one block kind and one
                    attention kind (window or full); Trinity's first 8:
                    2 dense window | 1 moe window | 1 moe full | 3 moe window
                    | 1 moe full
  ssm             : n_layers x [mamba2]
  hybrid (zamba2) : groups of `hybrid_attn_every` mamba2 layers, a SHARED
                    attention+ffn block (single param set, reused) after each
                    group, optionally fed concat(h, embed0) through a fuse
                    projection (Zamba's signature trick).

Scanning keeps the HLO O(1) in depth (compile-time requirement for the 61-layer
1T-param dry-run); jax.checkpoint wraps each block body per cfg.remat_policy.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common, ffn, moe, ssm


def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "save_dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # 'minimal': save only block inputs


# --------------------------------------------------------------------------
# Block bodies (mode: train | prefill | decode)
# --------------------------------------------------------------------------
def attn_ffn_block(params, x, cfg: ModelConfig, mode: str, cache, positions, key=None,
                   page_ctx=None):
    x = common.constrain_batch(x)
    h = common.rmsnorm(params["ln1"], x, cfg.norm_eps)
    if mode == "train":
        a = attention.apply_train(params["attn"], h, cfg, positions, key)
        new_cache = cache
    elif mode == "prefill":
        a, new_cache = attention.apply_prefill(params["attn"], h, cfg, cache, key)
    elif mode == "prefill_paged":   # cache: (stacked pools, layer index)
        a, new_cache = attention.apply_prefill_paged(
            params["attn"], h, cfg, *cache, page_ctx, key)
    elif mode == "decode_paged":
        a, new_cache = attention.apply_decode_paged(
            params["attn"], h, cfg, *cache, page_ctx, key)
    else:
        a, new_cache = attention.apply_decode(params["attn"], h, cfg, cache, key)
    if cfg.sandwich_norm:
        a = common.rmsnorm(params["ln_post_attn"], a, cfg.norm_eps)
    x = x + a
    h = common.rmsnorm(params["ln2"], x, cfg.norm_eps)
    aux = {}
    if "moe" in params:
        # Serving and calibration never drop a row: what a request gets
        # must not depend on what shares its step.
        f, aux = moe.apply(params["moe"], h, cfg, key, dropless=mode != "train")
    else:
        f = ffn.apply(params["ffn"], h, cfg, key)
    if cfg.sandwich_norm:
        f = common.rmsnorm(params["ln_post_ffn"], f, cfg.norm_eps)
    return x + f, new_cache, aux


def ssm_block(params, x, cfg: ModelConfig, mode: str, cache, key=None):
    if mode in ("prefill_paged", "decode_paged"):
        raise NotImplementedError(
            "paged serving covers attention families only for now; SSM state "
            "is O(1) per slot and the engine gates on cfg.family")
    x = common.constrain_batch(x)
    h = common.rmsnorm(params["ln"], x, cfg.norm_eps)
    if mode == "train":
        y = ssm.apply_train(params["ssm"], h, cfg, key)
        new_cache = cache
    elif mode == "prefill":
        y, new_cache = ssm.apply_prefill(params["ssm"], h, cfg, cache, key)
    else:
        y, new_cache = ssm.apply_decode(params["ssm"], h, cfg, cache, key)
    return x + y, new_cache


def _init_attn_ffn(key, cfg: ModelConfig, use_moe: bool, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": common.rmsnorm_init(cfg.d_model, dtype),
        "ln2": common.rmsnorm_init(cfg.d_model, dtype),
        "attn": attention.init(k1, cfg, dtype),
    }
    if cfg.sandwich_norm:
        p["ln_post_attn"] = common.rmsnorm_init(cfg.d_model, dtype)
        p["ln_post_ffn"] = common.rmsnorm_init(cfg.d_model, dtype)
    if use_moe:
        p["moe"] = moe.init(k2, cfg, dtype)
    else:
        p["ffn"] = ffn.init(k3, cfg, dtype=dtype)
    return p


def _init_ssm(key, cfg: ModelConfig, dtype):
    return {
        "ln": common.rmsnorm_init(cfg.d_model, dtype),
        "ssm": ssm.init(key, cfg, dtype),
    }


# --------------------------------------------------------------------------
# Segments: (kind, n_layers) with stacked params
# --------------------------------------------------------------------------
def segments(cfg: ModelConfig) -> list[tuple[str, int]]:
    if cfg.layer_types is not None:
        return [(kind, n) for kind, n, _ in _typed_runs(cfg)]
    if cfg.family in ("dense", "vlm", "audio"):
        return [("attn_ffn", cfg.n_layers)]
    if cfg.family == "moe":
        k = cfg.moe.first_k_dense
        segs = []
        if k:
            segs.append(("attn_ffn", k))
        segs.append(("attn_moe", cfg.n_layers - k))
        return segs
    if cfg.family == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        return [("hybrid", cfg.n_layers)]
    raise ValueError(cfg.family)


def _typed_runs(cfg: ModelConfig) -> list[tuple[str, int, bool]]:
    """Per-layer attention kinds: one segment per run of layers with the
    same block kind and the same attention kind, (kind, n, sliding) each,
    so that every scan is homogeneous."""
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"layer_types on family {cfg.family!r}")
    dense = cfg.moe.first_k_dense if cfg.family == "moe" else cfg.n_layers
    runs: list[list] = []
    for layer, sliding in enumerate(cfg.sliding_layers()):
        kind = "attn_ffn" if layer < dense else "attn_moe"
        if runs and runs[-1][0] == kind and runs[-1][2] == sliding:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1, sliding])
    return [tuple(r) for r in runs]


def segment_sliding(cfg: ModelConfig) -> list[bool]:
    """Per segment of ``segments(cfg)``: do its layers attend over the
    sliding window (and keep their KV in a window ring when served)?"""
    if cfg.layer_types is None:
        return [cfg.swa_window is not None] * len(segments(cfg))
    return [sliding for *_, sliding in _typed_runs(cfg)]


def segment_config(cfg: ModelConfig, sliding: bool) -> ModelConfig:
    """The configuration one segment's layers run under: a model with
    per-layer kinds keeps its window on sliding segments only."""
    if cfg.layer_types is None:
        return cfg
    return cfg.replace(layer_types=None,
                       swa_window=cfg.swa_window if sliding else None)


def _stacked_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init(key, cfg: ModelConfig, dtype) -> dict:
    params: dict[str, Any] = {}
    ks = jax.random.split(key, len(segments(cfg)) + 2)
    for i, (kind, n) in enumerate(segments(cfg)):
        if kind == "attn_ffn":
            params[f"seg{i}"] = _stacked_init(
                lambda k: _init_attn_ffn(k, cfg, False, dtype), ks[i], n)
        elif kind == "attn_moe":
            params[f"seg{i}"] = _stacked_init(
                lambda k: _init_attn_ffn(k, cfg, True, dtype), ks[i], n)
        elif kind == "ssm":
            params[f"seg{i}"] = _stacked_init(lambda k: _init_ssm(k, cfg, dtype), ks[i], n)
        elif kind == "hybrid":
            params[f"seg{i}"] = _stacked_init(lambda k: _init_ssm(k, cfg, dtype), ks[i], n)
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        kshared = jax.random.split(ks[-1], 2)
        params["shared_attn"] = _init_attn_ffn(kshared[0], cfg, False, dtype)
        if cfg.hybrid_concat_embed:
            params["fuse"] = common.dense_init(kshared[1], 2 * cfg.d_model, cfg.d_model, dtype)
    return params


# --------------------------------------------------------------------------
# Apply: scan over stacked segment params
# --------------------------------------------------------------------------
def _scan_segment(body, stacked_params, x, caches, cfg: ModelConfig):
    """caches: stacked pytree with leading layer dim (or None for train).

    The paged modes pass the layer indices here instead of their page
    pools: a pool in ``xs`` / ``ys`` would be sliced out of the stack,
    rewritten and stacked again by every layer, whole, for the few rows a
    step adds.  Their body reads the stacked pools it closes over and
    returns its new rows, which ``attention.paged_write`` stores once after
    the scan."""
    def step(carry, layer_in):
        p, c = layer_in
        new_x, new_c, aux = body(p, carry, c)
        return new_x, (new_c, aux)

    step = _remat(step, cfg) if cfg.remat_policy != "none" else step
    x, (new_caches, auxs) = jax.lax.scan(step, x, (stacked_params, caches))
    return x, new_caches, auxs


def apply(params, x: jax.Array, cfg: ModelConfig, mode: str,
          caches: Optional[dict], positions, embed0=None, key=None,
          page_ctx=None):
    """Run the full stack.  Returns (x, new_caches, aux_losses).

    ``page_ctx`` (``runtime.paged_cache.PrefillChunkCtx`` / ``DecodeCtx``)
    rides alongside the paged modes: the block table and positions are the
    same for every layer (pages are allocated per slot, not per layer), so
    the context is a loop-invariant side input rather than part of the
    scanned caches."""
    new_caches: dict[str, Any] = {}
    aux_total = {"lb_loss": jnp.zeros((), jnp.float32),
                 "z_loss": jnp.zeros((), jnp.float32)}
    paged = mode in ("prefill_paged", "decode_paged")
    sliding = segment_sliding(cfg)

    for i, (kind, n) in enumerate(segments(cfg)):
        seg_params = params[f"seg{i}"]
        seg_cache = None if caches is None else caches.get(f"seg{i}")

        if kind in ("attn_ffn", "attn_moe"):
            keys = ("lb_loss", "z_loss") + (
                ("moe_routed",) if kind == "attn_moe" else ())

            def body(p, h, c, _pools=seg_cache,
                     _cfg=segment_config(cfg, sliding[i]), _keys=keys):
                c = (_pools, c) if paged else c
                h2, nc, aux = attn_ffn_block(p, h, _cfg, mode, c, positions, key,
                                             page_ctx=page_ctx)
                aux = {k2: aux.get(k2, jnp.zeros(
                    (), jnp.int32 if k2 == "moe_routed" else jnp.float32))
                       for k2 in _keys}
                return h2, nc, aux
            x, nc, auxs = _scan_segment(
                body, seg_params, x,
                jnp.arange(n, dtype=jnp.int32) if paged else seg_cache, cfg)
            if kind == "attn_moe":
                aux_total = {k2: aux_total.get(k2, 0) + jnp.sum(auxs[k2])
                             for k2 in keys}
            if paged:
                nc = attention.paged_write(seg_cache, nc, page_ctx,
                                           ring=sliding[i])
            new_caches[f"seg{i}"] = nc

        elif kind == "ssm":
            def body(p, h, c):
                h2, nc = ssm_block(p, h, cfg, mode, c, key)
                return h2, nc, {"lb_loss": jnp.zeros((), jnp.float32),
                                "z_loss": jnp.zeros((), jnp.float32)}
            x, nc, _ = _scan_segment(body, seg_params, x, seg_cache, cfg)
            new_caches[f"seg{i}"] = nc

        elif kind == "hybrid":
            every = cfg.hybrid_attn_every or n
            n_groups = n // every
            # reshape stacked (n, ...) -> (n_groups, every, ...)
            gp = jax.tree.map(lambda a: a.reshape((n_groups, every) + a.shape[1:]),
                              seg_params)
            gc = None if seg_cache is None else jax.tree.map(
                lambda a: a.reshape((n_groups, every) + a.shape[1:]), seg_cache)
            shared_cache = None if caches is None else caches.get("shared_attn")
            shared_caches_out = []

            def ssm_body(p, h, c):
                h2, nc = ssm_block(p, h, cfg, mode, c, key)
                return h2, nc, {"lb_loss": jnp.zeros((), jnp.float32),
                                "z_loss": jnp.zeros((), jnp.float32)}

            group_caches = []
            for g in range(n_groups):
                gparams = jax.tree.map(lambda a: a[g], gp)
                gcache = None if gc is None else jax.tree.map(lambda a: a[g], gc)
                x, nc, _ = _scan_segment(ssm_body, gparams, x, gcache, cfg)
                group_caches.append(nc)
                # shared attention block (Zamba2): one param set reused
                h_in = x
                if cfg.hybrid_concat_embed and embed0 is not None:
                    h_in = common.dense(
                        params["fuse"],
                        jnp.concatenate([x, embed0], axis=-1),
                        cfg.site_tdvmm("hybrid.fuse"), key)
                sc = None if shared_cache is None else jax.tree.map(
                    lambda a: a[g], shared_cache)
                x, sc_new, _ = attn_ffn_block(
                    params["shared_attn"], h_in, cfg, mode, sc, positions, key)
                shared_caches_out.append(sc_new)
            new_caches[f"seg{i}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs).reshape((n,) + xs[0].shape[1:]) if xs[0] is not None else None,
                *group_caches) if group_caches and group_caches[0] is not None else None
            if shared_caches_out and shared_caches_out[0] is not None:
                new_caches["shared_attn"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *shared_caches_out)

    return x, new_caches, aux_total
