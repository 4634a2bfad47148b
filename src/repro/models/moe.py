"""Mixture-of-Experts with sort-based capacity dispatch.

Two distribution modes (cfg.moe.impl):

  'local' — experts replicated across the DP axes, expert-FFN hidden dim
            TP-sharded over `model` (fits small expert counts, e.g. Mixtral's
            8 experts on a 16-wide model axis).  Tokens never leave their DP
            shard; the only collective is the down-projection psum over
            `model`.

  'ep'    — expert tables sharded over the DP axes (E_loc = E / dp per shard;
            Kimi-K2: 384/16 = 24 per shard single-pod), hidden dim TP-sharded
            over `model`.  Tokens are routed to the shard owning their expert
            via all_to_all over the DP axes and routed back after the expert
            FFN — classic expert parallelism.

Dispatch is sort-based (argsort by expert id + rank-in-group + scatter into an
(E, capacity, d) buffer): no one-hot dispatch tensors, so it scales to E=384.
Both modes run inside shard_map; on a single device (tests) the same math runs
without collectives.

Training drops rows past ``capacity_factor``; serving and calibration
(``dropless``) size each expert's buffer at the step's rows, the most one
expert can get (a row picks an expert at most once), so no row is dropped
and a row's result does not depend on what shares its step.

An expert layer may hold a share of the experts (``MoEConfig.held`` from
``held_offset``, one chip's share under expert parallelism): it routes
every row over all ``n_experts`` and computes only its held experts' part;
a selection of an expert held elsewhere is left out, its weight unspent.
On one chip there is no exchange.

Router: softmax or sigmoid scores in float32, top-k selection on the
scores (plus a per-expert selection bias, ``router/b``, where
``selection_bias``), weights from the scores alone, normalised over the k
and scaled (``route_scale``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import calibration
from repro.launch import meshctx
from repro.models import common
from repro.runtime.trace import scope


def init(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    keys = jax.random.split(key, 5)
    gated = cfg.act == "silu_glu"
    scale = d ** -0.5

    def expert_bank(k, n):
        ks = jax.random.split(k, 3)
        p = {
            "w_up": (jax.random.normal(ks[0], (n, d, m.d_ff)) * scale).astype(dtype),
            "w_down": (jax.random.normal(ks[1], (n, m.d_ff, d)) * (m.d_ff ** -0.5)).astype(dtype),
        }
        if gated:
            p["w_gate"] = (jax.random.normal(ks[2], (n, d, m.d_ff)) * scale).astype(dtype)
        return p

    p = {
        "router": common.dense_init(keys[0], d, m.n_experts, jnp.float32,
                                    bias=m.selection_bias),
        "experts": expert_bank(keys[1], m.resolved_held),
    }
    if m.n_shared_experts:
        p["shared"] = expert_bank(keys[2], m.n_shared_experts)
    return p


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(n_tokens * top_k * factor / n_experts) + 1
    return max(c, 4)


def _expert_ffn(bank, x, cfg: ModelConfig, tp_axis: Optional[str], key=None,
                site_prefix: str = "moe.expert"):
    """x: (E, C, d) -> (E, C, d).  Hidden dim is TP-sharded when tp_axis given;
    the down-projection partial sums are reduced over tp (in bf16 when the
    matmul-out knob is set — halves the psum wire bytes).

    The up/gate projections resolve the ``<site_prefix>.in`` TD-VMM site and
    the down projection ``<site_prefix>.out`` (routed experts are
    ``moe.expert.*``, always-on shared experts ``moe.shared.*``).  With a
    site enabled, its matmul executes through the QuantizedTensor path
    (core/layers.td_expert_matmul): the expert dim maps onto the TD-VMM
    kernel's batched grid axis — one analog tile per expert — with int8 code
    storage, the backend knob, and (when calibrated) a per-expert
    (E,)-vector readout window honored.  Capacity-padded (ragged) expert
    rows are all-zero codes and contribute zero charge, so the dispatch
    buffer's padding stays exact.  ``key`` (train-time) draws independent
    programming noise per projection when the site's noise flag is on.
    """
    td_in = cfg.site_tdvmm(site_prefix + ".in")
    td_out = cfg.site_tdvmm(site_prefix + ".out")
    keys = iter(jax.random.split(key, 3)) if key is not None else None

    def mm(a, wmat, td):
        if td.enabled:
            from repro.core import layers as td_layers
            k = next(keys) if keys is not None else None
            return td_layers.td_expert_matmul(a, wmat, td, key=k)
        return jnp.einsum("ecd,edf->ecf", a, wmat)

    if "w_gate" in bank:
        h = jax.nn.silu(mm(x, bank["w_gate"], td_in))
        h = h * mm(x, bank["w_up"], td_in)
    else:
        h = common.activation(cfg.act, mm(x, bank["w_up"], td_in))
    y = mm(h, bank["w_down"], td_out)
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y


@scope("moe.route")
def _route(params, x_flat, cfg: ModelConfig):
    """Router, digital, in float32: returns (ids (T,K) over all n_experts,
    float32 weights (T,K), aux losses)."""
    m = cfg.moe
    logits = jnp.dot(x_flat.astype(jnp.float32), params["router"]["w"],
                     precision=jax.lax.Precision.HIGHEST)              # (T, E)
    if m.score_func == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    choice = probs + params["router"]["b"] if m.selection_bias else probs
    _, ids = jax.lax.top_k(choice, m.top_k)
    gates = jnp.take_along_axis(probs, ids, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9) * m.route_scale
    # Switch-style load-balance loss + router z-loss.  Expert counts via
    # scatter-add, NOT one_hot: a (T, K, E) one-hot is ~100 MB per layer per
    # microbatch at kimi-k2 scale.
    me = jnp.mean(probs, axis=0)                                       # (E,)
    counts = jnp.zeros((m.n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    ce = counts / ids.shape[0]
    lb_loss = m.n_experts * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return ids, gates, {"lb_loss": lb_loss, "z_loss": z_loss}


def _held_ids(ids: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Each selected expert's index among the experts this layer holds;
    ``held`` (one past the last) for an expert held elsewhere."""
    m = cfg.moe
    local = ids - m.held_offset
    return jnp.where((local >= 0) & (local < m.resolved_held), local,
                     m.resolved_held)


def _dispatch_indices(ids: jax.Array, top_k: int):
    """Sort-based dispatch bookkeeping.

    Returns (sorted_expert, pos_in_expert, order, token_idx): entry j of the
    sorted stream goes to buffer slot [sorted_expert[j], pos_in_expert[j]] and
    came from token token_idx[j]."""
    flat = ids.reshape(-1)                                             # (T*K,)
    order = jnp.argsort(flat)                                          # stable
    sorted_expert = flat[order]
    ranks = jnp.searchsorted(sorted_expert, sorted_expert, side="left")
    pos = jnp.arange(flat.shape[0]) - ranks
    token_idx = order // top_k
    return sorted_expert, pos, order, token_idx


def _scatter_to_buffer(x_flat, sorted_expert, pos, token_idx, n_experts, capacity):
    buf = jnp.zeros((n_experts, capacity) + x_flat.shape[1:], x_flat.dtype)
    return buf.at[sorted_expert, pos].set(x_flat[token_idx], mode="drop")


def _gather_from_buffer(buf, sorted_expert, pos, order, gates, top_k):
    """Inverse of the scatter; returns (T, d) combined output.

    Unsorting uses the inverse permutation as a GATHER (perf it.4): a scatter
    into a zeros buffer costs an extra zero-fill + random-write pass."""
    vals = buf[sorted_expert, jnp.minimum(pos, buf.shape[1] - 1)]      # (T*K, d)
    kept = (pos < buf.shape[1]) & (sorted_expert < buf.shape[0])
    vals = jnp.where(kept[:, None], vals, 0.0)
    inv_order = jnp.argsort(order)
    unsorted = vals[inv_order]
    per_k = unsorted.reshape(-1, top_k, vals.shape[-1])
    return jnp.sum(per_k.astype(jnp.float32) * gates[..., None].astype(
        jnp.float32), axis=1).astype(vals.dtype)


def _moe_local(params, x_flat, cfg: ModelConfig, tp_axis, key=None,
               dropless: bool = False):
    """Experts replicated over DP (or the held share); only collective is
    the tp psum.  aux gains ``moe_routed``: the buffer rows filled."""
    m = cfg.moe
    held = m.resolved_held
    ids, gates, aux = _route(params, x_flat, cfg)
    cap = x_flat.shape[0] if dropless else _capacity(
        x_flat.shape[0], m.top_k, m.n_experts, m.capacity_factor)
    with scope("moe.dispatch"):
        se, pos, order, tok = _dispatch_indices(_held_ids(ids, cfg), m.top_k)
        buf = _scatter_to_buffer(x_flat, se, pos, tok, held, cap)
        aux["moe_routed"] = jnp.sum((se < held) & (pos < cap), dtype=jnp.int32)
    out = _expert_ffn(params["experts"], buf, cfg, tp_axis, key=key)
    with scope("moe.combine"):
        y = _gather_from_buffer(out, se, pos, order, gates, m.top_k)
    return y, aux


def _moe_ep(params, x_flat, cfg: ModelConfig, tp_axis, dp_axes, dp_size,
            key=None):
    """Experts sharded over the DP axes; all_to_all routes tokens to owners."""
    m = cfg.moe
    e_loc = m.n_experts // dp_size
    ids, gates, aux = _route(params, x_flat, cfg)
    cap = _capacity(x_flat.shape[0], m.top_k, m.n_experts, m.capacity_factor)
    se, pos, order, tok = _dispatch_indices(ids, m.top_k)
    # send buffer grouped by destination shard: (E, C, d) == (dp, E_loc, C, d)
    buf = _scatter_to_buffer(x_flat, se, pos, tok, m.n_experts, cap)
    buf = buf.reshape(dp_size, e_loc, cap, -1)
    buf = jax.lax.all_to_all(buf, dp_axes, split_axis=0, concat_axis=0, tiled=False)
    # buf: (dp_src, E_loc, C, d) — tokens from every source shard for my experts
    buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, dp_size * cap, -1)
    out = _expert_ffn(params["experts"], buf, cfg, tp_axis, key=key)
    out = out.reshape(e_loc, dp_size, cap, -1).transpose(1, 0, 2, 3)
    out = jax.lax.all_to_all(out, dp_axes, split_axis=0, concat_axis=0, tiled=False)
    out = out.reshape(m.n_experts, cap, -1)
    y = _gather_from_buffer(out, se, pos, order, gates, m.top_k)
    return y, aux


def apply(params, x: jax.Array, cfg: ModelConfig, key=None,
          dropless: bool = False) -> tuple[jax.Array, dict]:
    """x: (B, S, d) -> (y, aux_losses).  ``key`` enables train-time TD-VMM
    programming noise on the expert (and shared-expert) matmuls when the
    resolved ``moe.expert.*`` / ``moe.shared.*`` site configs set noise.
    ``dropless``: every expert's buffer holds the call's rows (serving)."""
    m = cfg.moe
    b, s, d = x.shape
    mesh = meshctx.get_mesh()

    def _noisy(prefix):
        return any(td.enabled and td.noise for td in
                   (cfg.site_tdvmm(prefix + ".in"),
                    cfg.site_tdvmm(prefix + ".out")))

    # Split once so routed and shared experts draw independent noise; the
    # routed key is replicated into shard_map (noise must agree across tp
    # shards of one expert, and experts draw independently via array shape).
    k_shared = k_routed = None
    if key is not None and (_noisy("moe.expert") or _noisy("moe.shared")):
        k_shared, k_routed = jax.random.split(key)
    shared_y = 0.0
    if m.n_shared_experts:
        flat = x.reshape(1, b * s, d)
        shared_y = _expert_ffn(
            {k: v for k, v in params["shared"].items()}, flat, cfg, None,
            key=k_shared, site_prefix="moe.shared",
        ).reshape(b, s, d)
        # NB: shared-expert tp reduction is handled by GSPMD outside shard_map.

    if mesh is None:
        y, aux = _moe_local(params, x.reshape(-1, d), cfg, None, key=k_routed,
                            dropless=dropless)
        return y.reshape(b, s, d) + shared_y, aux
    if m.resolved_held != m.n_experts:
        raise NotImplementedError("a held share of the experts under a mesh")

    dp = meshctx.dp_axes()
    tp = meshctx.tp_axis()
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    # batch=1 decode (long_500k) can't split over dp: run replicated (the
    # dispatch is then redundant across dp shards but numerically identical).
    batch_spec = P(dp, None, None) if b % dp_size == 0 else P(None, None, None)
    e_ax = dp if m.impl == "ep" else None
    expert_spec = {
        k: (P(e_ax, tp, None) if k == "w_down" else P(e_ax, None, tp))
        for k in params["experts"]
    }
    router_spec = jax.tree.map(lambda _: P(None, None), params["router"])

    # Calibrated windows for the routed-expert sites ride in as EXPLICIT
    # shard_map operands, not closures: under impl='ep' a per-expert (E,)
    # window must arrive as each shard's local (E_loc,) slice — same layout
    # as the expert bank's leading dim — and a closure would capture the
    # full outer array on every shard.
    win_map = calibration.runtime_window_map() or {}
    expert_wins = {s: win_map[s] for s in ("moe.expert.in", "moe.expert.out")
                   if s in win_map}

    def _win_spec(w):
        nd = getattr(w, "ndim", 0)
        if e_ax is not None and nd == 1:    # (E,) sliced with the expert dim
            return P(e_ax)
        return P(*((None,) * nd))

    win_specs = {k: _win_spec(v) for k, v in expert_wins.items()}

    def inner(xb, experts, router, wins, *maybe_key):
        p = {"experts": experts, "router": router}
        kk = maybe_key[0] if maybe_key else None
        flat = xb.reshape(-1, d)
        # Re-install the expert windows from the per-shard operands so the
        # TD-VMM sites resolved inside this body see local slices (the outer
        # runtime_windows context still holds the unsharded arrays).
        with calibration.runtime_windows(wins if wins else None):
            if m.impl == "ep":
                if kk is not None:
                    # Each dp shard owns a *different* expert slice: fold the
                    # shard index in so experts draw independent noise.  (Local
                    # mode must NOT fold — experts there are replicated and all
                    # shards need bitwise-identical noise.)
                    for a in dp:
                        kk = jax.random.fold_in(kk, jax.lax.axis_index(a))
                y, aux = _moe_ep(p, flat, cfg, tp, dp, dp_size, key=kk)
            else:
                y, aux = _moe_local(p, flat, cfg, tp, key=kk,
                                    dropless=dropless)
                aux.pop("moe_routed")       # a per-shard count
        aux = jax.tree.map(lambda v: jax.lax.pmean(v, dp), aux)
        return y.reshape(xb.shape), aux

    in_specs = (batch_spec, expert_spec, router_spec, win_specs)
    args = (x, params["experts"], params["router"], expert_wins)
    if k_routed is not None:
        in_specs += (P(),)          # noise key: replicated across the mesh
        args += (k_routed,)
    y, aux = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(batch_spec, P()),
        check_vma=False,
    )(*args)
    return y + shared_y, aux
