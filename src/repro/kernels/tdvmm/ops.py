"""jit'd public wrapper around the TD-VMM matmul kernel (+ scales epilogue).

This is the *integrate + readout* tail of the code-and-scale pipeline
(core/quant.py): integer code matrices in, model-unit outputs out.

    acc = x_codes @ w_codes          charge accumulation (Eq. 1)
    z   = acc * gain                 latch normalization (crossing time)
    z   = readout(z, out_bits)       p-bit shared-counter ADC (Eq. 3, §4.2)
    y   = z * x_scale[:, None] * w_scale[None, :]   digital rescale

The readout happens on the latch-normalized accumulation — the ADC samples
the crossing *time*, before any per-row/per-channel digital rescale — so the
epilogue carries per-row input scales and per-channel weight scales through
without changing what the hardware quantizes.

Code dtypes (``code_dtype``): ``"int8"`` stores the codes as int8 in HBM
(quarter the f32 bytes) and accumulates exactly in int32 on both backends —
the MXU int8 path on TPU, an s8 x s8 -> s32 dot under XLA elsewhere — so the
backends are bit-for-bit identical for *any* K with |acc| < 2^31, with no
2^24 f32 envelope.  ``"int4"`` (codes with |code| <= 7, p <= 3) additionally
packs two codes per byte for the Pallas stream (``core.quant.pack_int4``,
unpacked in-kernel) — half the int8 bytes, still exact int32 accumulation,
bit-for-bit identical to int8.  ``"f32"`` is the legacy float-code path
(8-bit codes, noise-perturbed analog currents); exact only while
|acc| < 2^24.  ``"auto"`` follows the input arrays' dtypes.

Epilogue placement (Pallas backend): every readout runs inside the
kernel's final K step (tdvmm_fused_kernel), so each output tile
materializes in HBM exactly once, already in model units.  A *fixed*
readout window (``out_scale`` or ``out_window`` given) or no readout is one
launch.  A data-calibrated window (``out_scale=None`` with ``out_bits``) is
two: ``tdvmm_absmax_kernel`` writes the per-tile max|z|, which is reduced
per readout slot into the window the fused launch then reads.  The jnp
backend runs the same epilogue (``_epilogue``) on the einsum accumulation;
every epilogue evaluates the same expression term for term — the window's
reciprocal comes from one XLA expression, ``tdvmm.readout_factors``, on
every path — so the backends are bit-for-bit identical.

Batching: 3-D inputs (E, M, K) x (E, K, N) map the expert dim onto the
kernel's batched grid axis (scales (E, M) / (E, N)); 2-D inputs run as E=1.
A 2-D x against a 3-D (G, K, N) weight bank runs the **shared-input grouped**
grid — the paper's shared-DAC dataflow: one (M, K) code matrix (and one
(M,) scale vector) feeds all G weight tiles in a single launch, returning
(G, M, N).  Per-group w_scale/out_scale ride the same (G, ...) operands as
per-expert batching.

Ragged grouped launches (``group_widths``): G same-input projections of
uneven widths concatenate along N into ONE 2-D (M, K) x (K, sum N_g) launch
— each member zero-padded only to the 128 lane, not to the widest member —
with per-member readout windows addressed by column span (a tuple
``out_scale`` maps per member; data calibration reduces per member).  This
is how ``core.layers.td_grouped_matmul`` runs attn.qkv / ssm.in_proj without
padding every member to max(N_g).

Block sizes: ``plan_kernel`` resolves the backend and consults the
per-platform autotune tables (tdvmm.autotune_lookup), records every lookup
in ``autotune_report()``, and warns ONCE per untuned shape instead of
silently falling back to heuristic blocks.

Gradients flow through a shared custom VJP (plain matmul cotangents on the
STE-wrapped codes, identity through the readout quantizer), so every backend
x dtype combination is trainable and backend-independent in the
backward pass.  Pass int arrays directly only on no-grad (serving) paths;
the QAT path feeds the f32 STE view and lets the forward cast to int8.
"""
from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tdvmm.tdvmm import (
    acc_dtype_for, autotune_blocks, autotune_lookup, autotune_platform,
    pad_to_blocks, readout_factors, tdvmm_absmax_kernel, tdvmm_fused_kernel)
from repro.runtime.trace import scope


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    """'auto' | 'jnp' | 'pallas' -> concrete integrate implementation.

    Shape-aware form: ``plan_kernel`` additionally consults the block-size
    autotune tables (kernels/tdvmm/autotune_table.py) keyed on
    (M, K, N, dtype).
    """
    if backend == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown TD-VMM backend {backend!r}")
    return backend


class KernelPlan(NamedTuple):
    """Resolved backend + autotuned block sizes for one codes matmul."""
    backend: str
    bm: int
    bk: int
    bn: int
    code_dtype: str = "f32"
    autotune_hit: bool = False   # False = heuristic fallback (untuned shape)
    platform: str = "interpret"  # which autotune table answered

    @property
    def blocks(self) -> tuple[int, int, int]:
        return (self.bm, self.bk, self.bn)


# Every plan_kernel lookup of this process, keyed (M, K, N, dtype-name) —
# the kernel report that makes untuned (heuristic-fallback) shapes visible
# (``autotune_report``) instead of quietly slow.
_AUTOTUNE_LOG: dict[tuple[int, int, int, str], dict] = {}
_AUTOTUNE_WARNED: set[tuple[int, int, int, str]] = set()
_logger = logging.getLogger(__name__)


def plan_kernel(backend: str, m: int, k: int, n: int,
                code_dtype: str = "f32") -> KernelPlan:
    """resolve_backend + the (M, K, N, dtype)-keyed block autotune table.

    Records the lookup (blocks, hit/miss, platform) into
    ``autotune_report()`` and warns once per untuned shape — run
    ``scripts/autotune_tdvmm.py`` to backfill the table."""
    name = "float32" if code_dtype in ("f32", "auto") else code_dtype
    platform = autotune_platform()
    blocks, hit = autotune_lookup(m, k, n, name, platform)
    key = (m, k, n, name)
    _AUTOTUNE_LOG[key] = {"blocks": blocks, "hit": hit, "platform": platform}
    if not hit and key not in _AUTOTUNE_WARNED:
        _AUTOTUNE_WARNED.add(key)
        # One-time log (not warnings.warn: planning runs on hot, otherwise
        # warning-free paths); the miss also lands in autotune_report().
        _logger.warning(
            "TD-VMM autotune miss: no %s table entry for (M, K, N, dtype)="
            "(%d, %d, %d, %s); using heuristic blocks %s.  Run "
            "scripts/autotune_tdvmm.py to tune this shape.",
            platform, m, k, n, name, blocks)
    return KernelPlan(resolve_backend(backend), *blocks,
                      code_dtype=code_dtype, autotune_hit=hit,
                      platform=platform)


def autotune_report() -> dict:
    """Every (M, K, N, dtype) this process planned, with the chosen blocks
    and whether the autotune table answered — benches attach this to their
    JSON report so CI sees exactly which shapes ran untuned."""
    entries = {
        f"{m}x{k}x{n}:{name}": dict(v)
        for (m, k, n, name), v in sorted(_AUTOTUNE_LOG.items())}
    return {"platform": autotune_platform(),
            "entries": entries,
            "misses": sorted(k for k, v in entries.items() if not v["hit"])}


def reset_autotune_report() -> None:
    _AUTOTUNE_LOG.clear()


def _member_window_cols(values, group_widths, n: int) -> jax.Array:
    """(G,) per-member window values -> a (1, 1, N) per-column vector over
    the ragged concat span (pad columns get 1.0 — they only ever multiply
    zero-code outputs)."""
    parts = [jnp.full((wd,), np.float32(v), jnp.float32)
             for v, wd in zip(values, group_widths)]
    tail = n - sum(group_widths)
    if tail:
        parts.append(jnp.ones((tail,), jnp.float32))
    return jnp.concatenate(parts).reshape(1, 1, n)


def _member_window_cols_arr(values: jax.Array, group_widths,
                            n: int) -> jax.Array:
    """Traced sibling of ``_member_window_cols``: a (G,) window *array*
    gathered out to the (1, 1, N) per-column vector (pad columns 1.0).  The
    gather index is host-static, so the expansion adds no data-dependent
    shapes — a hot-swapped window recompiles nothing."""
    idx = []
    for g, wd in enumerate(group_widths):
        idx.extend([g] * wd)
    idx.extend([len(group_widths)] * (n - sum(group_widths)))
    vals = jnp.concatenate([
        jnp.asarray(values, jnp.float32).reshape(-1),
        jnp.ones((1,), jnp.float32)])
    return vals[jnp.asarray(np.asarray(idx, np.int32))].reshape(1, 1, n)


# ---------------------------------------------------------------------------
# Epilogue (unfused form; the fused kernels mirror this term for term)
# ---------------------------------------------------------------------------
def _epilogue(acc, x_scale, w_scale, gain, out_bits, out_scale,
              group_widths=None, out_window=None):
    """gain -> optional p-bit readout -> per-row x per-channel rescale.

    acc: (E, M, N) int32 or f32; x_scale: (E, M); w_scale: (E, N).
    ``out_scale=None`` calibrates the ADC window to max|z| *per expert tile*
    (each expert is its own analog array; E=1 reproduces the global window).
    A tuple ``out_scale`` is an (E,)-vector of fixed per-expert windows —
    one calibrated readout window per expert's analog tile.  With
    ``group_widths`` (ragged concat launch) windows are per *member column
    span* instead: a tuple maps one window per member, and data calibration
    reduces max|z| over each member's columns.  ``out_window`` is the traced
    *array* form of a fixed window (scalar / (E,) / per-member (G,)): same
    expression, window as a runtime operand instead of a baked constant —
    serving hot-swaps calibration values through it without recompiling.
    """
    # Pin the inputs and (acc * gain) as units: under a caller's jit the
    # latch gain and the caller's scale chains are visible to XLA, which
    # sinks their constant factors through the readout multiplies — e.g.
    # (w_scale * 2K) * back reassociates into w_scale * (2K * back), 1 ulp
    # off the eager / in-kernel association.
    x_scale = jax.lax.optimization_barrier(x_scale.astype(jnp.float32))
    w_scale = jax.lax.optimization_barrier(w_scale.astype(jnp.float32))
    z = jax.lax.optimization_barrier(
        acc.astype(jnp.float32) * jnp.float32(gain))
    ws_row = w_scale[..., None, :]
    if out_bits is not None:
        # Bit-for-bit contract: a calibration-pinned window must reproduce
        # the per-call data-calibrated window it was captured from, and the
        # fused Pallas epilogues must match this unfused form exactly.  Two
        # XLA behaviors break that if window-derived factors enter the graph
        # as literals: division by a constant strength-reduces into a
        # 1-ulp-off reciprocal multiply, and constant factors get
        # reassociated (sunk) through neighboring multiply chains.  So the
        # window is always a *runtime* value (constants pass through an
        # optimization_barrier), divisions are explicit, and the post-round
        # rescale chain ``(q * xs) * (ws * back)`` carries no constants —
        # matching the fused kernels' association term for term.
        s = out_scale
        if out_window is not None:
            # Runtime window: already a traced value, so the barrier chain
            # below sees exactly what the static path sees post-barrier —
            # the two programs are the same arithmetic term for term.
            ow = jnp.asarray(out_window, jnp.float32)
            if group_widths is not None:
                s = _member_window_cols_arr(ow, group_widths, z.shape[-1])
            elif ow.ndim >= 1:
                s = ow.reshape(-1, 1, 1)
            else:
                s = ow
        elif s is None:
            if group_widths is not None:
                # Per-member windows over the concat columns: f32 max is
                # exact, so the per-span reduction equals each member's
                # standalone max bit for bit.
                off, segs = 0, []
                for wd in group_widths:
                    seg = jnp.max(jnp.abs(z[..., off:off + wd]),
                                  axis=(-2, -1), keepdims=True, initial=0.0)
                    segs.append(jnp.broadcast_to(
                        seg, seg.shape[:-1] + (wd,)))
                    off += wd
                s = jnp.concatenate(segs, axis=-1)
            else:
                s = jnp.max(jnp.abs(z), axis=(-2, -1), keepdims=True,
                            initial=0.0)
            s = jax.lax.stop_gradient(jnp.maximum(s, 1e-9))
        elif isinstance(s, tuple):
            if group_widths is not None:
                s = _member_window_cols(s, group_widths, z.shape[-1])
            else:
                s = jnp.asarray(s, jnp.float32).reshape(-1, 1, 1)
        else:
            s = jnp.float32(s)
        # The barriers in readout_factors pin mul(z, inv): XLA otherwise
        # strength-reduces mul(z, div(1, s)) back into div(z, s) — 1 ulp
        # off, and only in programs where s is a scalar broadcast, so a
        # grouped (vector window) launch and its sequential counterpart
        # would disagree.  The window is widened to one value per column
        # first, as the fused kernels take it: on a TPU, XLA divides a
        # scalar on the scalar unit, whose rounding differs from the vector
        # unit's.
        s = jnp.broadcast_to(jnp.asarray(s, jnp.float32),
                             z.shape[:-2] + (1, z.shape[-1]))
        inv, back = readout_factors(s, out_bits)
        levels = float((1 << out_bits) - 1)
        z = jnp.round(jnp.clip(z * inv, -1.0, 1.0) * levels)
        ws_row = jax.lax.optimization_barrier(ws_row * back)
    # Pin (z * xs) before the ws_row multiply: with both factors broadcasts,
    # XLA reassociates the chain shape-dependently; the kernels' in-VMEM
    # epilogues evaluate exactly this association, term for term.
    zx = jax.lax.optimization_barrier(z * x_scale[..., :, None])
    return zx * ws_row


def _slot_windows(tile_max: jax.Array, bn: int, n: int,
                  group_widths) -> jax.Array:
    """Per-tile maxima (E, M // bn_m, N // bn) -> (E|1, 1, N) per-column
    data-calibrated windows: the max over each readout slot — the whole
    expert tile for batched launches, the owning member's column span for
    ragged ones (pad-tail blocks fold into the last member; their zero
    accumulators can't move an abs-max) — floored as in ``_epilogue``."""
    col = jnp.max(tile_max, axis=1)                    # (E, N // bn)
    e, nn = col.shape
    if group_widths is None:
        s = jnp.broadcast_to(jnp.max(col, axis=1).reshape(e, 1, 1), (e, 1, n))
    else:
        ids = np.searchsorted(np.cumsum(group_widths), np.arange(nn) * bn,
                              side="right")
        ids = np.minimum(ids, len(group_widths) - 1)
        member = jnp.stack([jnp.max(col[0, np.flatnonzero(ids == g)])
                            for g in range(len(group_widths))])
        s = _member_window_cols_arr(member, group_widths, n)
    return jnp.maximum(s, 1e-9)


def _whole_on_each_device(kernel, *operands):
    """Run one kernel launch under the active serving/training mesh.

    GSPMD cannot partition a Mosaic kernel (lowering refuses a launch in a
    multi-device program outside shard_map), so under a mesh each device
    runs the whole launch on replicated operands inside a shard_map — the
    same arithmetic as one device, so sharded and meshless results stay
    bit-identical.  Launches already inside a manual region (the MoE
    shard_map body) are per-shard by construction and run as they are."""
    from repro.launch import meshctx
    mesh = meshctx.get_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return kernel(*operands)
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*operands)


def _tdvmm_impl(x_codes, w_codes, x_scale, w_scale, gain, out_bits,
                out_scale, out_window, backend, interpret, code_dtype,
                blocks, group_widths):
    ex, m, k = x_codes.shape
    e, _, n = w_codes.shape
    shared_x = ex == 1 and e > 1
    assert ex == e or shared_x, (x_codes.shape, w_codes.shape)
    if min(e, m, k, n) == 0:
        # Empty expert batch / filtered serving batch / zero-width contraction:
        # zero charge everywhere, and readout(0) * scales == 0 on every path.
        return jnp.zeros((e, m, n), jnp.float32)
    code = jnp.int8 if code_dtype in ("int8", "int4") else jnp.float32
    # Integer codes lie within the storage range by the caller's contract
    # (p <= 7 / p <= 3); the cast is exact and XLA fuses it into the
    # producer, so the kernel streams 1-byte codes from HBM.
    xi = x_codes.astype(code)
    with scope("weight_program"):
        wi = w_codes.astype(code)
    if blocks is None:
        blocks = autotune_blocks(
            m, k, n, "int4" if code_dtype == "int4" else xi.dtype)
    bm, bk, bn = blocks

    if backend == "jnp":
        if shared_x:
            # Same contraction (and accumulation order) as the batched form,
            # with the single code matrix broadcast over the G weight tiles.
            acc = jnp.einsum("mk,gkn->gmn", xi[0], wi,
                             preferred_element_type=acc_dtype_for(xi.dtype))
        else:
            acc = jnp.einsum("emk,ekn->emn", xi, wi,
                             preferred_element_type=acc_dtype_for(xi.dtype))
        return _epilogue(acc, x_scale, w_scale, gain, out_bits, out_scale,
                         group_widths, out_window)

    unpack4 = code_dtype == "int4"
    if unpack4:
        # Two codes per byte for the HBM stream; launch geometry (K, bk)
        # switches to packed units — the kernel unpacks per block.
        from repro.core.quant import pack_int4
        xi = pack_int4(xi, axis=-1)
        with scope("weight_program"):
            wi = pack_int4(wi, axis=-2)
        bk = max(bk // 2, 1)
    xp, wp = pad_to_blocks(xi, wi, bm, bk, bn)
    mp, np_ = xp.shape[-2], wp.shape[-1]
    exact = (mp, np_) == (m, n)

    # Every readout runs in the fused kernel's epilogue: the (bm, bn) tile
    # leaves VMEM exactly once, already in model units.
    xsp = jnp.pad(x_scale, ((0, 0), (0, mp - m)))[..., :, None]
    wsp = jnp.pad(w_scale, ((0, 0), (0, np_ - n)))[..., None, :]
    window, scale_arg = None, out_scale
    if out_bits is not None and out_window is not None:
        # Fixed window as a runtime operand.
        ow = jnp.asarray(out_window, jnp.float32)
        if group_widths is not None:
            window = _member_window_cols_arr(ow, group_widths, np_)
        else:
            window = ow.reshape(-1, 1, 1) if ow.ndim >= 1 \
                else ow.reshape(1, 1, 1)
        scale_arg = None
    elif (out_bits is not None and group_widths is not None
            and isinstance(out_scale, tuple)):
        window, scale_arg = _member_window_cols(
            out_scale, group_widths, np_), None
    elif out_bits is not None and out_scale is None:
        # Data-calibrated window: per-tile maxima, reduced per readout
        # slot, become the fused launch's window.
        tile_max = _whole_on_each_device(functools.partial(
            tdvmm_absmax_kernel, gain=gain, bm=bm, bk=bk, bn=bn,
            interpret=interpret, unpack4=unpack4), xp, wp)
        window = _slot_windows(tile_max, min(bn, np_), np_, group_widths)
    y = _whole_on_each_device(functools.partial(
        tdvmm_fused_kernel, gain=gain, out_bits=out_bits,
        out_scale=scale_arg, bm=bm, bk=bk, bn=bn, interpret=interpret,
        unpack4=unpack4), xp, wp, xsp, wsp, window)
    return y if exact else y[:, :m, :n]


# ---------------------------------------------------------------------------
# Shared custom VJP (all backends / dtypes)
# ---------------------------------------------------------------------------
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _tdvmm_core(x_codes, w_codes, x_scale, w_scale, out_window, gain,
                out_bits, out_scale, backend, interpret, code_dtype, blocks,
                group_widths):
    """Differentiable integrate+epilogue on canonical (E, M, K) shapes.

    ``out_window`` rides as a differentiable-position arg (it is traced —
    nondiff_argnums must stay hashable statics) but is calibration state,
    not a trainable: its cotangent is zeros, matching the static-window
    path where the window never enters the autodiff graph at all."""
    return _tdvmm_impl(x_codes, w_codes, x_scale, w_scale, gain, out_bits,
                       out_scale, out_window, backend, interpret, code_dtype,
                       blocks, group_widths)


def _tdvmm_core_fwd(x_codes, w_codes, x_scale, w_scale, out_window, gain,
                    out_bits, out_scale, backend, interpret, code_dtype,
                    blocks, group_widths):
    y = _tdvmm_impl(x_codes, w_codes, x_scale, w_scale, gain, out_bits,
                    out_scale, out_window, backend, interpret, code_dtype,
                    blocks, group_widths)
    return y, (x_codes, w_codes, x_scale, w_scale, out_window, y)


def _tdvmm_core_bwd(gain, out_bits, out_scale, backend, interpret,
                    code_dtype, blocks, group_widths, res, g):
    x_codes, w_codes, x_scale, w_scale, out_window, y = res
    denom = x_scale[..., :, None] * w_scale[..., None, :]
    # Recover the post-readout latch value z = y / (xs * ws); internal
    # callers clamp scales >= 1e-6, so the where() only guards direct API
    # calls with exact-zero scales (whose y, and scale grads, are both 0).
    z = jnp.where(denom == 0.0, 0.0, y / denom)
    # Identity through the readout quantizer (STE) and the latch gain:
    dacc = g * denom * gain
    xf = x_codes.astype(jnp.float32)
    wf = w_codes.astype(jnp.float32)
    if x_codes.shape[0] == 1 and dacc.shape[0] > 1:
        # Shared-input grouped launch: the one x (and x_scale) fed every
        # group tile, so its cotangent sums over the group axis.  (Ragged
        # concat launches are plain 2-D matmuls here: member columns sum
        # into the shared x cotangent through the ordinary contraction.)
        gx = jnp.einsum("gmn,gkn->mk", dacc, wf,
                        preferred_element_type=jnp.float32)[None]
        gw = jnp.einsum("mk,gmn->gkn", xf[0], dacc,
                        preferred_element_type=jnp.float32)
        gxs = jnp.sum(g * z * w_scale[..., None, :], axis=(0, -1))[None]
    else:
        gx = jnp.einsum("emn,ekn->emk", dacc, wf,
                        preferred_element_type=jnp.float32)
        gw = jnp.einsum("emk,emn->ekn", xf, dacc,
                        preferred_element_type=jnp.float32)
        gxs = jnp.sum(g * z * w_scale[..., None, :], axis=-1)
    gws = jnp.sum(g * z * x_scale[..., :, None], axis=-2)
    gwin = None if out_window is None else jnp.zeros_like(out_window)
    return gx, gw, gxs, gws, gwin


_tdvmm_core.defvjp(_tdvmm_core_fwd, _tdvmm_core_bwd)


def codes_matmul(
    x_codes: jax.Array, w_codes: jax.Array, backend: str,
    interpret: bool | None = None, code_dtype: str = "auto",
) -> jax.Array:
    """Raw (.., M, K) @ (.., K, N) charge accumulation as f32, padded to the
    kernel's block multiples and sliced back.  Differentiable on any backend
    (custom VJP = plain matmul cotangents, matching jnp.dot autodiff).

    A 2-D x against a 3-D (G, K, N) bank runs shared-x grouped: one code
    matrix against G tiles, returning (G, M, N) (no squeeze)."""
    squeeze = x_codes.ndim == 2 and w_codes.ndim == 2
    if x_codes.ndim == 2:
        x_codes = x_codes[None]
    if w_codes.ndim == 2:
        w_codes = w_codes[None]
    m = x_codes.shape[1]
    e, _, n = w_codes.shape
    if interpret is None:
        interpret = not _on_tpu()
    if code_dtype == "auto":
        code_dtype = "int8" if jnp.issubdtype(
            x_codes.dtype, jnp.integer) else "f32"
    ones_m = jnp.ones((x_codes.shape[0], m), jnp.float32)
    ones_n = jnp.ones((e, n), jnp.float32)
    acc = _dispatch(x_codes, w_codes, ones_m, ones_n, 1.0, None, None, None,
                    resolve_backend(backend), bool(interpret), code_dtype,
                    None, None)
    return acc[0] if squeeze else acc


def _dispatch(x_codes, w_codes, x_scale, w_scale, gain, out_bits, out_scale,
              out_window, backend, interpret, code_dtype, blocks,
              group_widths):
    """Route int inputs straight to the impl (no float cotangents exist);
    float inputs go through the shared custom VJP."""
    if jnp.issubdtype(x_codes.dtype, jnp.integer):
        return _tdvmm_impl(x_codes, w_codes, x_scale, w_scale, gain,
                           out_bits, out_scale, out_window, backend,
                           interpret, code_dtype, blocks, group_widths)
    return _tdvmm_core(x_codes, w_codes, x_scale, w_scale, out_window, gain,
                       out_bits, out_scale, backend, interpret, code_dtype,
                       blocks, group_widths)


@functools.partial(
    jax.jit,
    static_argnames=("gain", "out_bits", "out_scale", "backend", "interpret",
                     "code_dtype", "block_sizes", "group_widths"))
def tdvmm_matmul(
    x_codes: jax.Array,      # (M, K) or (E, M, K) signed time codes
    w_codes: jax.Array,      # (K, N) or (E, K, N) signed weight codes
    x_scale: jax.Array,      # (M,) / (E, M) per-row input scales
    w_scale: jax.Array,      # (N,) / (E, N) per-channel weight scales
    gain: float = 1.0,
    out_bits: int | None = None,
    out_scale: float | tuple[float, ...] | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
    code_dtype: str = "auto",
    block_sizes: tuple[int, int, int] | None = None,
    group_widths: Optional[tuple[int, ...]] = None,
    out_window: Optional[jax.Array] = None,
) -> jax.Array:
    """Quantized four-quadrant TD-VMM: codes matmul + readout + scale epilogue.

    ``out_scale=None`` calibrates the readout window from the data (§3.1) —
    on the Pallas backend via a per-tile max launch plus the fused kernel; pass
    the value captured by ``core.layers.calibrate_out_scale`` (or the
    model-wide calibration pass) to skip the per-call max entirely.  A tuple
    is an (E,)-vector of fixed per-expert windows for batched inputs — still
    static, still fused.  Arbitrary M/K/N are zero-padded to the kernel's
    block shape; ``block_sizes=None`` consults the autotune table.

    ``out_window`` is the *traced-array* form of a fixed window — scalar
    ``()``, per-expert ``(E,)``, or per-member ``(G,)`` on ragged grouped
    launches.  It is NOT a jit-static argument: swapping window values of
    the same shape reuses the compiled program (the serving engine's
    hot-swappable calibration), and the epilogue evaluates the identical
    barrier-pinned expression as the static ``out_scale`` path, so the two
    forms are bit-for-bit interchangeable.  Mutually exclusive with
    ``out_scale``; requires ``out_bits``.

    Shared-x grouped: a 2-D (M, K) x against a 3-D (G, K, N) weight bank
    (x_scale (M,), w_scale (G, N)) runs one launch whose G tiles all read
    the same code matrix, returning (G, M, N) un-squeezed.

    Ragged grouped: ``group_widths=(N_1, ..., N_G)`` declares a 2-D
    (M, K) x (K, sum N_g) launch as the column concat of G same-input
    members; readout windows (tuple ``out_scale``, or data calibration)
    resolve per member column span instead of per launch.
    """
    backend = resolve_backend(backend)
    if interpret is None:
        interpret = not _on_tpu()
    squeeze = x_codes.ndim == 2 and w_codes.ndim == 2
    if x_codes.ndim == 2:
        x_codes = x_codes[None]
    if w_codes.ndim == 2:
        w_codes = w_codes[None]
    ex, m, _ = x_codes.shape
    e, _, n = w_codes.shape
    if ex not in (e, 1):
        raise ValueError(
            f"batched x/w mismatch: x batch {ex} vs w batch {e} "
            "(shared-x grouped launches carry a single x batch entry)")
    if group_widths is not None:
        group_widths = tuple(int(w) for w in group_widths)
        if ex != 1 or e != 1:
            raise ValueError(
                "group_widths describes a 2-D ragged concat launch; got "
                f"batched codes (x batch {ex}, w batch {e})")
        if sum(group_widths) != n:
            raise ValueError(
                f"group_widths {group_widths} sum to {sum(group_widths)} "
                f"but the concat weight bank has N={n}")
        if isinstance(out_scale, tuple) and len(out_scale) != len(group_widths):
            raise ValueError(
                f"out_scale has {len(out_scale)} member windows for "
                f"{len(group_widths)} group members")
    elif isinstance(out_scale, tuple) and len(out_scale) != e:
        raise ValueError(
            f"out_scale has {len(out_scale)} per-expert windows for "
            f"E={e} batched tiles")
    if out_window is not None:
        if out_bits is None:
            raise ValueError("out_window needs out_bits (p-bit readout)")
        if out_scale is not None:
            raise ValueError(
                "out_window and out_scale are mutually exclusive (the "
                "window array is the runtime-operand form of out_scale)")
        out_window = jnp.asarray(out_window, jnp.float32)
        if group_widths is not None:
            if out_window.shape != (len(group_widths),):
                raise ValueError(
                    f"out_window shape {out_window.shape} for a "
                    f"{len(group_widths)}-member grouped launch; "
                    f"expected ({len(group_widths)},)")
        elif out_window.ndim == 1 and out_window.shape[0] != e:
            raise ValueError(
                f"out_window has {out_window.shape[0]} per-expert windows "
                f"for E={e} batched tiles")
        elif out_window.ndim > 1:
            raise ValueError(
                f"out_window must be scalar, (E,) or (G,); got shape "
                f"{out_window.shape}")
    if code_dtype == "auto":
        code_dtype = "int8" if jnp.issubdtype(
            x_codes.dtype, jnp.integer) else "f32"
    x_scale = x_scale.reshape(ex, m).astype(jnp.float32)
    w_scale = w_scale.reshape(e, n).astype(jnp.float32)
    y = _dispatch(x_codes, w_codes, x_scale, w_scale, gain, out_bits,
                  out_scale, out_window, backend, bool(interpret),
                  code_dtype, block_sizes, group_widths)
    # lax.squeeze, not y[0]: integer indexing lowers to a full-range slice
    # copy of the (M, N) output before the squeeze view.
    return jax.lax.squeeze(y, (0,)) if squeeze else y
