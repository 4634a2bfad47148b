"""LM wrapper: embedding, stack, head, loss; train/prefill/decode entry points."""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common, ssm, transformer
from repro.runtime.trace import scope


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = common.resolve_dtype(cfg.dtype)
    ke, kb, kh = jax.random.split(key, 3)
    params: dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        params["embed"] = {
            "table": (jax.random.normal(ke, (cfg.padded_vocab, cfg.d_model)) * 0.02
                      ).astype(dtype)}
    params["blocks"] = transformer.init(kb, cfg, dtype)
    params["ln_f"] = common.rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["head"] = common.dense_init(kh, cfg.d_model, cfg.padded_vocab, dtype,
                                           scale=cfg.d_model ** -0.5)
    return params


def _embed(params, batch: dict, cfg: ModelConfig) -> jax.Array:
    if cfg.input_mode == "tokens":
        x = params["embed"]["table"][batch["inputs"]]
        if cfg.embed_scale:
            x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(x.dtype)
        return x
    return batch["inputs"].astype(common.resolve_dtype(cfg.dtype))


def _head(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ params["embed"]["table"].T
    return common.dense(params["head"], x, cfg.site_tdvmm("head"))


def forward(params, batch: dict, cfg: ModelConfig, key=None):
    """Training forward: full-sequence causal.  Returns (logits, aux)."""
    x = _embed(params, batch, cfg)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, _, aux = transformer.apply(params["blocks"], x, cfg, "train", None,
                                  positions, embed0=x, key=key)
    x = common.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return _head(params, x, cfg), aux


def loss_fn(params, batch: dict, cfg: ModelConfig, key=None,
            lb_coef: float = 0.01, z_coef: float = 1e-3):
    """Next-token cross-entropy with padding mask; targets: (B, S) int32,
    positions with target < 0 are masked out."""
    logits, aux = forward(params, batch, cfg, key)
    targets = batch["targets"]
    mask = (targets >= 0).astype(jnp.float32)
    safe_t = jnp.maximum(targets, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_t[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom
    total = loss + lb_coef * aux["lb_loss"] + z_coef * aux["z_loss"]
    metrics = {"loss": loss, "lb_loss": aux["lb_loss"], "z_loss": aux["z_loss"],
               "tokens": mask.sum()}
    return total, metrics


# --------------------------------------------------------------------------
# Serving
# --------------------------------------------------------------------------
def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dtype = common.resolve_dtype(cfg.dtype)
    sliding = transformer.segment_sliding(cfg)

    def one_attn(i=0):
        scfg = transformer.segment_config(cfg, sliding[i])
        return attention.init_cache(scfg, batch, max_len, dtype)

    def one_ssm():
        return ssm.init_cache(cfg, batch, dtype)

    def stack(mk, n):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[mk() for _ in range(n)])

    caches: dict[str, Any] = {}
    for i, (kind, n) in enumerate(transformer.segments(cfg)):
        if kind in ("attn_ffn", "attn_moe"):
            caches[f"seg{i}"] = stack(lambda i=i: one_attn(i), n)
        elif kind == "ssm":
            caches[f"seg{i}"] = stack(one_ssm, n)
        elif kind == "hybrid":
            caches[f"seg{i}"] = stack(one_ssm, n)
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        n_groups = cfg.n_layers // cfg.hybrid_attn_every
        caches["shared_attn"] = stack(one_attn, n_groups)
    return caches


def prefill_step(params, batch: dict, caches: dict, cfg: ModelConfig,
                 calib=None):
    """Absorb a prompt.  Returns (logits_last, new_caches).

    ``calib`` (a ``core.calibration.CalibrationState``) pins each TD-VMM
    site's readout window: the per-call max|z| reduction disappears and the
    Pallas fused-epilogue kernel becomes eligible.  Windows are baked in as
    jit-static site overrides, so pass concrete (non-traced) state — close
    over it when jitting, don't thread it as a jit argument."""
    from repro.core.calibration import apply_calibration
    cfg = apply_calibration(cfg, calib)
    x = _embed(params, batch, cfg)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x, new_caches, _ = transformer.apply(params["blocks"], x, cfg, "prefill",
                                         caches, positions, embed0=x)
    x = common.rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    return _head(params, x, cfg), new_caches


def decode_step(params, batch: dict, caches: dict, cfg: ModelConfig,
                calib=None):
    """One token for every sequence.  batch['inputs']: (B, 1) (or (B,1,d) for
    embedding-input archs).  Returns (logits, new_caches).  ``calib`` as in
    ``prefill_step``."""
    from repro.core.calibration import apply_calibration
    cfg = apply_calibration(cfg, calib)
    x = _embed(params, batch, cfg)
    b = x.shape[0]
    positions = None  # decode blocks read positions from their caches
    x, new_caches, _ = transformer.apply(params["blocks"], x, cfg, "decode",
                                         caches, positions, embed0=x)
    x = common.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return _head(params, x, cfg), new_caches


# --------------------------------------------------------------------------
# Paged serving (continuous-batching engine, runtime/engine.py)
# --------------------------------------------------------------------------
def init_paged_caches(cfg: ModelConfig, num_pages: int, page_size: int,
                      ranks: int = 1, ring_pages: int = 0) -> dict:
    """Page pools for every attention layer (attention families only).

    Unlike ``init_caches`` there is no batch/max_len here: capacity is the
    shared pool, and per-request footprint is decided at admission time by
    the engine's block tables.  All layers share one logical page allocation
    (the same page id addresses the same token range in every layer's pool).
    Window layers' segments hold window rings instead, ``ring_pages`` pages
    in all (``runtime.paged_cache.ring_pages`` a slot).  An MoE model's
    caches also carry ``moe_routed``: rows its steps routed to the experts
    this chip holds, summed on the device.
    """
    if cfg.family not in ("dense", "moe", "vlm", "audio"):
        raise NotImplementedError(
            f"paged serving supports attention families, not {cfg.family!r} "
            "(SSM state is O(1) per slot; use the static path)")
    dtype = common.resolve_dtype(cfg.dtype)

    def stacked(n, pages):
        # One zero allocation per stacked pool: stacking n per-layer pools
        # would hold twice the pools' bytes while it copies.
        return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype),
                            jax.eval_shape(lambda: attention.init_paged_cache(
                                cfg, pages, page_size, dtype, ranks=ranks)))

    sliding = transformer.segment_sliding(cfg)
    if any(sliding) and ring_pages < 1:
        raise ValueError("window layers need ring_pages > 0")
    caches: dict[str, Any] = {}
    for i, (kind, n) in enumerate(transformer.segments(cfg)):
        if kind not in ("attn_ffn", "attn_moe"):
            raise NotImplementedError(f"paged serving: segment kind {kind!r}")
        caches[f"seg{i}"] = stacked(n, ring_pages if sliding[i] else num_pages)
    if cfg.family == "moe":
        caches["moe_routed"] = jnp.zeros((), jnp.int32)
    return caches


def _count_routed(caches: dict, new_caches: dict, aux: dict) -> None:
    """Carry the device-side count of routed expert rows across a step."""
    if "moe_routed" in caches:
        new_caches["moe_routed"] = caches["moe_routed"] + aux["moe_routed"]


def prefill_chunk(params, batch: dict, caches: dict, cfg: ModelConfig,
                  calib=None, windows=None):
    """One fixed-shape prefill chunk for ONE slot (the engine's first
    compiled step).  batch: {"inputs": (1, C) tokens, "block_row": (P,),
    "offset": (), "valid": ()}.  Returns (logits at the last valid position
    — shape (1, 1, V) — and the updated page pools).  ``calib`` as in
    ``prefill_step`` (close over concrete state at jit time).

    ``windows`` (site -> f32 window array, ``CalibrationState.as_arrays()``)
    is the *hot-swappable* alternative: the windows enter the compiled
    program as runtime operands (thread the dict as a jit argument), so the
    engine can recalibrate between steps without recompiling — bit-identical
    to the baked ``calib`` path."""
    from repro.core import calibration
    from repro.core.calibration import apply_calibration
    from repro.runtime.paged_cache import PrefillChunkCtx
    cfg = apply_calibration(cfg, calib)
    ctx = PrefillChunkCtx(block_row=batch["block_row"],
                          offset=batch["offset"], valid=batch["valid"],
                          ring_row=batch.get("ring_row"))
    with calibration.runtime_windows(windows):
        x = _embed(params, batch, cfg)
        x, new_caches, aux = transformer.apply(params["blocks"], x, cfg,
                                               "prefill_paged", caches, None,
                                               embed0=x, page_ctx=ctx)
        _count_routed(caches, new_caches, aux)
        # logits only at the chunk's last real token (== prefill_step's
        # x[:, -1:] on the final chunk); padded rows never reach the head.
        with scope("head"):
            x = jax.lax.dynamic_slice_in_dim(x, ctx.valid - 1, 1, axis=1)
            x = common.rmsnorm(params["ln_f"], x, cfg.norm_eps)
            return _head(params, x, cfg), new_caches


def decode_slots(params, batch: dict, caches: dict, cfg: ModelConfig,
                 calib=None, windows=None):
    """One token for every occupied slot (the engine's second compiled
    step).  batch: {"inputs": (B, 1) tokens, "block_tables": (B, P),
    "pos": (B,), "active": (B,) bool}.  Returns (logits (B, 1, V), updated
    page pools); inactive rows produce ignored logits.  ``windows`` as in
    ``prefill_chunk`` (runtime-operand readout windows)."""
    from repro.core import calibration
    from repro.core.calibration import apply_calibration
    from repro.runtime.paged_cache import DecodeCtx
    cfg = apply_calibration(cfg, calib)
    ctx = DecodeCtx(block_tables=batch["block_tables"], pos=batch["pos"],
                    active=batch["active"], ring_tables=batch.get("ring_tables"))
    with calibration.runtime_windows(windows):
        x = _embed(params, batch, cfg)
        x, new_caches, aux = transformer.apply(params["blocks"], x, cfg,
                                               "decode_paged", caches, None,
                                               embed0=x, page_ctx=ctx)
        _count_routed(caches, new_caches, aux)
        with scope("head"):
            x = common.rmsnorm(params["ln_f"], x, cfg.norm_eps)
            return _head(params, x, cfg), new_caches


def calibrate(params, batch: dict, cfg: ModelConfig, max_len: int = 0):
    """Model-wide §3.1 readout-window calibration (one prefill pass).

    Runs ``prefill_step`` over a representative batch with the calibration
    collector installed: every enabled, digital-boundary TD-VMM site records
    the max|z| of its latch-normalized accumulation — scalar per site,
    ``(E,)`` per-expert for expert-batched sites (one window per analog
    tile; layers scanned into one site max-merge).  Returns the captured
    ``CalibrationState``; persist it with
    ``checkpoint.checkpoint.save_calibration`` and hand it back to
    ``prefill_step`` / ``decode_step`` / ``launch.serve`` for serving.
    """
    from repro.core import calibration
    b, s = batch["inputs"].shape[:2]
    caches = init_caches(cfg, b, max_len or s)
    with calibration.collect() as collected:
        prefill_step(params, batch, caches, cfg)
    return calibration.CalibrationState.from_collected(collected)


def drift_probe(params, batch: dict, cfg: ModelConfig, pinned,
                max_len: int = 0):
    """One eager calibration pass measured *against* pinned windows.

    Same capture as ``calibrate`` but with clip tracking on: every site
    additionally tallies how much of its latch-normalized |z| mass exceeds
    the window currently pinned for serving (``pinned``: a
    ``CalibrationState``).  Returns ``(fresh, clip_rates)`` — the freshly
    captured ``CalibrationState`` and a site -> clip-fraction dict — the two
    signals the engine's drift detector thresholds to decide when the §3.1
    windows have gone stale.  Eager (outside the engine's two compiled
    steps), so probing never adds a compiled program."""
    import numpy as np

    from repro.core import calibration
    b, s = batch["inputs"].shape[:2]
    caches = init_caches(cfg, b, max_len or s)
    ref = {site: np.asarray(v, np.float32)
           for site, v in pinned.windows.items()}
    with calibration.collect(pinned=ref) as collected:
        prefill_step(params, batch, caches, cfg)
    fresh = calibration.CalibrationState.from_collected(collected)
    clips = calibration.last_clips() or {}
    return fresh, calibration.clip_rates(clips)
