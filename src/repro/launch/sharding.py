"""Parameter / state / batch sharding rules (GSPMD logical-axis mapping).

Strategy (MaxText-style 2D/3D hybrid):
  * batch            -> all DP axes ('pod','data')
  * FSDP (ZeRO-3)    -> params' non-TP matrix dim sharded over the DP axes
  * TP               -> heads / ffn-hidden / vocab dim over 'model'
  * MoE expert banks -> impl 'ep': expert dim over DP axes; hidden over 'model'
                        impl 'local': replicated expert dim, FSDP d, TP hidden

Rules are written against the TRAILING dims of each weight; scanned stacks
(leading n_layers dim) get None padded on the left automatically, so the same
rule covers stacked and unstacked instances.
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import axis_info


def _rules(fsdp, tp, ep):
    """(regex over '/'-joined path) -> trailing-dims PartitionSpec entries."""
    return [
        # MoE expert banks (3D: E, d_in, d_out)
        (r"moe/experts/w_(up|gate)$", (ep, None, tp)),
        (r"moe/experts/w_down$", (ep, tp, None)),
        (r"moe/shared/w_(up|gate)$", (None, fsdp, tp)),
        (r"moe/shared/w_down$", (None, tp, fsdp)),
        (r"moe/router/w$", (None, None)),
        # attention
        (r"attn/w[qkv]/w$", (fsdp, tp)),
        (r"attn/w[qkv]/b$", (tp,)),
        (r"attn/wo/w$", (tp, fsdp)),
        (r"attn/wo/b$", (None,)),
        # ffn
        (r"ffn/w_(up|gate)/w$", (fsdp, tp)),
        (r"ffn/w_down/w$", (tp, fsdp)),
        # ssm
        (r"ssm/w[zx]/w$", (fsdp, tp)),
        (r"ssm/w[BC]/w$", (fsdp, tp)),
        (r"ssm/wdt/w$", (fsdp, tp)),
        (r"ssm/wo/w$", (tp, fsdp)),
        (r"ssm/conv_w$", (None, None, tp)),
        (r"ssm/conv_b$", (tp,)),
        (r"ssm/(A_log|D|dt_bias)$", (None,)),
        # embeddings / head / fuse
        (r"embed/table$", (tp, fsdp)),
        (r"head/w$", (fsdp, tp)),
        (r"fuse/w$", (fsdp, tp)),
        # norms and everything 1D
        (r"(scale|b)$", (None,)),
    ]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
    return "/".join(parts)


def param_specs(params_shape: Any, cfg: ModelConfig, mesh: Mesh,
                dp_axes: tuple[str, ...] | None = None,
                layer_axis: str | None = None,
                ep_axes: tuple[str, ...] | None = None):
    """PartitionSpec pytree matching the params pytree.

    dp_axes: override the FSDP axes (pipeline parallelism uses 'pod' as the
    stage axis, so FSDP shrinks to ('data',); the serving engine passes ()
    to replicate weights over DP — no ZeRO-3 gathers in the step).
    layer_axis: if given, scanned-stack leaves (leading n_layers dim) get this
    mesh axis on dim 0 — the PP stage layout.
    ep_axes: override the expert-bank axes independently of FSDP (serving
    keeps dense weights DP-replicated but still shards expert tables over
    the DP axes under ``moe.impl='ep'``)."""
    info = axis_info(mesh)
    fsdp = (info["dp_axes"] if dp_axes is None else dp_axes) or None
    tp = info["tp_axis"]
    ep_base = fsdp if ep_axes is None else (ep_axes or None)
    ep = ep_base if (cfg.moe is not None and cfg.moe.impl == "ep") else None
    rules = _rules(fsdp, tp, ep)

    def spec_for(path, leaf):
        s = _path_str(path)
        for pat, trailing in rules:
            if re.search(pat, s):
                nd = len(leaf.shape)
                if len(trailing) > nd:   # unstacked smaller leaf (e.g. scalars)
                    trailing = trailing[-nd:] if nd else ()
                pad = list((None,) * (nd - len(trailing)))
                if layer_axis and pad and "/seg" in s:
                    pad[0] = layer_axis   # stage dim over 'pod' (PP layout)
                return P(*(tuple(pad) + tuple(trailing)))
        return P(*((None,) * len(leaf.shape)))

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def opt_state_specs(opt_shape: Any, p_specs: Any):
    """Optimizer state shares its params' sharding; adafactor's factored
    moments drop the corresponding dim of the param spec."""
    import jax.tree_util as jtu

    p_leaves = {_path_str(p): s for p, s in
                jtu.tree_flatten_with_path(p_specs)[0]}

    def spec_for(path, leaf):
        s = _path_str(path)
        # step counter / scalars
        if not leaf.shape:
            return P()
        # path looks like 'inner/m/<param path>' or 'inner/<param path>/vr' etc.
        m = re.match(r"inner/(m|v)/(.*)$", s)
        if m and m.group(2) in p_leaves:
            return p_leaves[m.group(2)]
        m = re.match(r"inner/(.*)/(m|vr|vc|v)$", s)
        if m and m.group(1) in p_leaves:
            base = tuple(p_leaves[m.group(1)])
            kind = m.group(2)
            if kind in ("m", "v"):
                return P(*base)
            if kind == "vr":
                return P(*base[:-1])
            if kind == "vc":
                return P(*(base[:-2] + base[-1:]))
        return P(*((None,) * len(leaf.shape)))

    return jax.tree_util.tree_map_with_path(spec_for, opt_shape)


def _dp_size(mesh: Mesh, dp) -> int:
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    return n


def batch_specs(cfg: ModelConfig, mesh: Mesh, kind: str, global_batch: int):
    dp = axis_info(mesh)["dp_axes"]
    if global_batch % _dp_size(mesh, dp) != 0:
        dp = None   # e.g. long_500k's batch=1: replicate batch, shard the cache
    if cfg.input_mode == "tokens":
        inp = P(dp, None)
    else:
        inp = P(dp, None, None)
    if kind in ("decode", "prefill"):
        return {"inputs": inp}
    return {"inputs": inp, "targets": P(dp, None)}


def cache_specs(cache_shape: Any, cfg: ModelConfig, mesh: Mesh):
    """KV caches: batch over DP and kv-heads over TP when divisible; falls back
    to sequence-sharding (SP) the cache / head_dim-sharding otherwise (e.g.
    long_500k's batch=1, or kv=8 on a 16-wide model axis)."""
    info = axis_info(mesh)
    dp, tp = info["dp_axes"], info["tp_axis"]
    dpn = _dp_size(mesh, dp)
    tpn = mesh.shape[tp] if tp else 1

    def spec_for(path, leaf):
        s = _path_str(path)
        nd = len(leaf.shape)
        if s.endswith("/pos") or nd <= 1:
            return P(*((None,) * nd))
        if re.search(r"/(k|v)$", s):          # (L, B, S, KV, HD)
            L, B, S, KV, HD = leaf.shape
            b_ax = dp if B % dpn == 0 else None
            s_ax = dp if (b_ax is None and S % dpn == 0) else None
            kv_ax = tp if KV % tpn == 0 else None
            hd_ax = tp if (kv_ax is None and HD % tpn == 0) else None
            return P(None, b_ax, s_ax, kv_ax, hd_ax)
        if re.search(r"/(k_scale|v_scale)$", s):   # (L, B, S, KV)
            L, B, S, KV = leaf.shape
            b_ax = dp if B % dpn == 0 else None
            s_ax = dp if (b_ax is None and S % dpn == 0) else None
            return P(None, b_ax, s_ax, tp if KV % tpn == 0 else None)
        if s.endswith("/conv"):               # (L, B, W, C)
            L, B, W, C = leaf.shape
            b_ax = dp if B % dpn == 0 else None
            return P(None, b_ax, None, tp if C % tpn == 0 else None)
        if s.endswith("/state"):              # (L, B, H, P, S)
            L, B, H, Pp, S = leaf.shape
            b_ax = dp if B % dpn == 0 else None
            return P(None, b_ax, tp if H % tpn == 0 else None, None, None)
        return P(*((None,) * nd))

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def paged_specs(cache_shape: Any, cfg: ModelConfig, mesh: Mesh):
    """Paged KV pools: head dims over TP, the page pool itself replicated.

    Paged leaves are (L, pages, page_size, KV*HD) — the leading ``pages``
    dim is a global pool indexed through host-built block tables, so it must
    NOT be sharded (every device gathers arbitrary page ids; the DP slot-pool
    dimension lives in the *block tables*, not the pool).  The heads dim goes
    over TP when KV divides evenly, so that each device holds whole heads;
    else the pool is replicated.  Per-position int8 KV scales (L, pages,
    page_size, KV) follow their pool.
    """
    info = axis_info(mesh)
    tp = info["tp_axis"]
    tpn = mesh.shape[tp] if tp else 1

    def spec_for(path, leaf):
        s = _path_str(path)
        nd = len(leaf.shape)
        if re.search(r"/(k|v)$", s):          # (L, pages, ps, KV*HD)
            return P(None, None, None,
                     tp if cfg.n_kv_heads % tpn == 0 else None)
        if re.search(r"/(k_scale|v_scale)$", s):   # (L, pages, ps, KV)
            L, PG, PS, KV = leaf.shape
            return P(None, None, None, tp if KV % tpn == 0 else None)
        return P(*((None,) * nd))

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def slot_specs(mesh: Mesh, kind: str):
    """Engine step-batch layouts for the DP slot-pool dimension.

    decode: batch rows ARE the slots, ordered (dp_rank, local_slot), so the
    leading dim shards over DP — inputs/block_tables (B, ·), pos/active (B,).
    prefill: one slot per step (batch 1) — fully replicated.
    """
    dp = axis_info(mesh)["dp_axes"] or None
    if kind == "prefill":
        return {"inputs": P(None, None), "block_row": P(None),
                "offset": P(), "valid": P()}
    if kind != "decode":
        raise ValueError(f"unknown engine step kind {kind!r}")
    return {"inputs": P(dp, None), "block_tables": P(dp, None),
            "pos": P(dp), "active": P(dp)}


def to_named(spec_tree: Any, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def sds_with_sharding(shape_tree: Any, sharding_tree: Any):
    return jax.tree.map(
        lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh),
        shape_tree, sharding_tree)
