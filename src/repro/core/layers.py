"""TDVMMLinear: the paper's multiplier as a drop-in linear layer for models.

td_matmul is the *closed form* of the four-quadrant TD-VMM (exact by Eq. 1-7,
property-tested against the event-driven simulator in tdcore.py), structured
as the explicit code-and-scale pipeline of core/quant.py:

    plan         flatten (..., N_in) to 2-D, pick code storage (int8 when the
                 signed code range fits — exact int32 accumulation, no 2^24
                 envelope — else f32), resolve the integrate backend + block
                 sizes from the autotune table
    encode       x -> p-bit signed time codes + per-row scale   (Eq. 2, DAC)
    program      W -> signed current codes + per-channel scale  (FG tuning)
    integrate    codes matmul — kernels/tdvmm (Pallas on TPU, interpret
                 elsewhere) or jnp.dot; identical integer arithmetic
    readout      latch normalization + p-bit ADC over the calibrated output
                 window when the tile boundary is digital      (Eq. 3, §4.2)
    rescale      digital per-row x per-channel rescale to model units

With a *fixed* readout window (``cfg.out_scale``, captured once by
``calibrate_out_scale`` / ``TDVMMLinear.calibrate`` on the serving path) the
Pallas backend fuses readout + rescale into the kernel's final K step, so
each output tile is written to HBM exactly once.

``td_expert_matmul`` is the batched (E, C, K) x (E, K, N) form for MoE
expert banks: one analog tile per expert, per-expert scales, the expert dim
mapped onto the kernel's batched grid axis.  ``td_grouped_matmul`` is the
shared-input sibling: G same-input projection matrices (attention q/k/v, the
SSM in_proj fan-out) concatenate along N into one ragged 2-D launch — each
member rounded only to the 128 lane, not to the widest member — while the
input is encoded once and read by every column — the paper's shared-DAC
amortization at the model level, one kernel dispatch instead of G.

Gradients: straight-through estimators on every quantizer (standard QAT) and
a plain-matmul custom VJP on the integrate stage, so the layer is trainable
inside any JAX model on either backend.  Optional stochastic DIBL / tuning
noise (core/nonideal.py) models deploy-time precision during training (noisy
codes are non-integer and force the f32 code path).

Arbitrary leading batch dims and non-block-multiple shapes are supported:
codes are flattened to (M, K) and zero-padded to the kernel's block multiples
(a zero time code contributes zero charge, so padding is exact).
"""
from __future__ import annotations

import math
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TDVMMLayerConfig  # re-export (historic home)
from repro.core import quant
from repro.runtime.trace import scope

__all__ = ["TDVMMLayerConfig", "td_matmul", "td_expert_matmul",
           "td_grouped_matmul", "calibrate_out_scale", "TDVMMLinear",
           "init_linear"]


class MatmulPlan(NamedTuple):
    """Static shape/backend/storage bookkeeping for one td_matmul call."""
    batch_shape: tuple[int, ...]     # leading dims of x, flattened into M
    m: int
    k: int                           # N_in: sources per output column
    n: int
    backend: str                     # resolved: "jnp" | "pallas"
    code_dtype: str                  # "int4" | "int8" | "f32" code storage
    blocks: tuple[int, int, int]     # autotuned (bm, bk, bn)


def _plan_code_dtype(cfg: TDVMMLayerConfig, k: int, noisy: bool) -> str:
    """Pick the code storage for a K-deep accumulation, warning only on the
    f32 fallback (the int8/int32 path is exact, so it never warns)."""
    lx = (1 << cfg.bits) - 1
    lw = (1 << cfg.weight_bits) - 1
    worst = lx * lw * max(k, 1)
    # int8 storage: both code ranges fit int8 (quant.storage_dtype owns that
    # rule), codes stay on the integer grid (no analog noise), and the
    # worst-case |acc| fits int32 — then accumulation is exact for ANY K,
    # no envelope to warn about.
    fits_int8 = (quant.storage_dtype(cfg.bits) == jnp.int8
                 and quant.storage_dtype(cfg.weight_bits) == jnp.int8)
    if not noisy and fits_int8 and worst < (1 << 31):
        # p <= 3 on both operands fits a signed nibble: the Pallas stream
        # packs two codes per byte (half the int8 HBM bytes) and unpacks
        # in-kernel — still exact int32 accumulation, bit-for-bit vs int8.
        if cfg.bits <= quant.INT4_MAX_BITS and \
                cfg.weight_bits <= quant.INT4_MAX_BITS:
            return "int4"
        return "int8"
    # f32 integer-exactness envelope: the backend-parity guarantee (and exact
    # charge accumulation) needs worst-case |acc| < 2^24.  6-bit codes are
    # safe to K = 4096; 8-bit only to K ~ 258.
    if worst >= (1 << 24):
        warnings.warn(
            f"TD-VMM f32 accumulator may exceed f32 integer range: "
            f"(2^{cfg.bits}-1)*(2^{cfg.weight_bits}-1)*K={worst} >= 2^24; "
            "charge sums can round and jnp/pallas backends may diverge",
            stacklevel=3)
    return "f32"


def plan_matmul(x_shape, w_shape, cfg: TDVMMLayerConfig,
                noisy: bool = False) -> MatmulPlan:
    k, n = w_shape
    assert x_shape[-1] == k, (x_shape, w_shape)
    batch_shape = tuple(x_shape[:-1])
    m = 1
    for d in batch_shape:
        m *= d
    code_dtype = _plan_code_dtype(cfg, k, noisy)
    from repro.kernels.tdvmm import ops
    kp = ops.plan_kernel(cfg.backend, m, k, n, code_dtype)
    return MatmulPlan(batch_shape, m, k, n, kp.backend, code_dtype, kp.blocks)


def _readout_args(
    cfg: TDVMMLayerConfig, n_experts: Optional[int] = None
) -> tuple[Optional[int], Optional[float | tuple[float, ...]]]:
    """(out_bits, out_scale) for the kernel epilogue.  Priority: a cached
    calibration window (cfg.out_scale) > data calibration (None, §3.1) > the
    fixed 0.5 raw differential window of a normalized tile.

    ``cfg.out_scale`` may be an (E,)-tuple of per-expert windows on
    expert-batched sites; ``n_experts`` validates the pairing (None = a 2-D
    site, where only a scalar window is meaningful).
    """
    if not cfg.io_quantize:
        return None, None
    if cfg.out_scale is not None:
        s = cfg.out_scale
        if isinstance(s, tuple):
            if n_experts is None:
                if len(s) != 1:
                    raise ValueError(
                        f"site {cfg.site or '<unnamed>'}: per-expert "
                        f"out_scale tuple (len {len(s)}) on a non-batched "
                        "matmul; expected a scalar window")
                return cfg.bits, float(s[0])
            if len(s) != n_experts:
                raise ValueError(
                    f"site {cfg.site or '<unnamed>'}: out_scale has "
                    f"{len(s)} windows for {n_experts} experts")
            return cfg.bits, tuple(float(v) for v in s)
        return cfg.bits, float(s)
    return cfg.bits, (None if cfg.output_calibration else 0.5)


def _runtime_override(cfg: TDVMMLayerConfig, out_bits, out_scale):
    """Swap a site's static readout window for the runtime-operand array
    installed by ``calibration.runtime_windows`` (the serving engine's
    hot-swappable calibration channel).  Outside that context — or for
    sites without a digital readout — this is a no-op passthrough."""
    if out_bits is None:
        return out_scale, None
    from repro.core import calibration
    rw = calibration.runtime_window(cfg.site)
    if rw is None:
        return out_scale, None
    return None, rw


def _latch_gain(levels_x: int, levels_w: int, k: int) -> float:
    """Latch gain: codes -> normalized differential output z = y+ - y- in
    [-1, 1]: divide out both code ranges and the 2*N_in charge headroom."""
    return 1.0 / (float(levels_x) * float(levels_w) * 2.0 * max(k, 1))


def _record_window(cfg: TDVMMLayerConfig, x_view, w_view, backend: str,
                   code_dtype: str, gain: float, per_tile: bool,
                   group_widths: Optional[tuple[int, ...]] = None) -> None:
    """Calibration capture: when a ``core.calibration`` collector is active
    and the site has a digital readout boundary, record its latch-normalized
    max|z| — a scalar, the per-expert-tile ``(E,)`` vector when ``per_tile``,
    or the per-member ``(G,)`` vector over a ragged concat launch's column
    spans (``group_widths``) — exactly the window per-call data calibration
    would use.  Costs one extra codes matmul per site, paid only during the
    (one-time) calibration pass.

    Under ``collect(pinned=...)`` (a drift probe) the same pass also tallies
    the site's readout *clip count* — how many |z| elements exceed the
    currently pinned window — feeding the saturation-rate drift trigger."""
    from repro.core import calibration
    if not calibration.active() or not cfg.io_quantize:
        return
    from repro.kernels.tdvmm import ops
    acc = ops.codes_matmul(x_view, w_view, backend, code_dtype=code_dtype)
    z = jnp.abs(acc.astype(jnp.float32) * gain)
    ref = calibration.clip_reference(cfg.site)
    if ref is not None:
        if group_widths is not None:
            # Per-member windows expand to per-column thresholds; pad
            # columns threshold at +inf (zero charge, never a clip).
            cols = np.concatenate(
                [np.full(wd, float(v), np.float32) for v, wd in
                 zip(np.asarray(ref, np.float32).reshape(-1), group_widths)])
            tail = z.shape[-1] - cols.size
            if tail > 0:
                cols = np.concatenate(
                    [cols, np.full(tail, np.inf, np.float32)])
            thresh = jnp.asarray(cols)
        elif per_tile:
            thresh = jnp.asarray(ref, jnp.float32).reshape(-1, 1, 1)
        else:
            thresh = jnp.float32(np.float32(ref))
        calibration.record_clip(cfg.site, jnp.sum(z > thresh), int(z.size))
    if group_widths is not None:
        # Member g owns columns [off, off + width_g); pad columns are zero
        # charge, so the span max equals the member's standalone max.
        off, maxes = 0, []
        for wd in group_widths:
            maxes.append(jnp.max(z[..., off:off + wd], initial=0.0))
            off += wd
        calibration.record(cfg.site, jnp.stack(maxes))
        return
    calibration.record(
        cfg.site,
        jnp.max(z, axis=((-2, -1) if per_tile else None), initial=0.0))


def td_matmul(
    x: jax.Array,
    w: jax.Array,
    cfg: TDVMMLayerConfig,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Four-quadrant TD-VMM fast path.  x: (..., N_in), w: (N_in, N_out)."""
    if not cfg.enabled:
        return x @ w

    with scope("tdvmm"):
        noisy = cfg.noise and key is not None

        # ---- plan: shapes + code storage + backend/blocks ----
        plan = plan_matmul(x.shape, w.shape, cfg, noisy=noisy)

        # ---- encode inputs / program weights (core/quant.py stages) ----
        qx = quant.encode_input(x, cfg.bits)
        qw = quant.program_weights(w, cfg.weight_bits, cfg.per_channel)
        if noisy:
            qw = quant.program_noise(qw, cfg.spec, key)

        # ---- integrate + readout + rescale (kernel epilogue) ----
        from repro.kernels.tdvmm import ops
        gain = _latch_gain(qx.levels, qw.levels, plan.k)
        # Digital rescale: per-row input range and per-channel 2*N_in*w_max.
        w_scale = jnp.broadcast_to(
            qw.scale.reshape(-1) * (2.0 * plan.k), (plan.n,))
        out_bits, out_scale = _readout_args(cfg)
        out_scale, out_window = _runtime_override(cfg, out_bits, out_scale)
        _record_window(cfg, qx.view().reshape(plan.m, plan.k), qw.view(),
                       plan.backend, plan.code_dtype, gain, per_tile=False)
        y = ops.tdvmm_matmul(
            qx.view().reshape(plan.m, plan.k),
            qw.view(),
            qx.scale.reshape(plan.m),
            w_scale,
            gain=gain,
            out_bits=out_bits,
            out_scale=out_scale,
            backend=plan.backend,
            code_dtype=plan.code_dtype,
            block_sizes=plan.blocks,
            out_window=out_window,
        )
        return y.reshape(plan.batch_shape + (plan.n,)).astype(x.dtype)


def td_expert_matmul(
    x: jax.Array,            # (E, C, N_in) expert-batched activations
    w: jax.Array,            # (E, N_in, N_out) stacked expert weight bank
    cfg: TDVMMLayerConfig,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Batched four-quadrant TD-VMM: one analog tile per expert.

    The MoE dispatch buffer multiplies against every expert's weight matrix
    in one kernel launch — the expert dim rides the kernel's batched grid
    axis, with per-expert-per-row input scales and per-expert-per-channel
    weight scales.  Zero-padded (ragged) expert rows carry zero codes and
    contribute zero charge, so capacity padding is exact.
    """
    if not cfg.enabled:
        return jnp.einsum("eck,ekn->ecn", x, w)

    with scope("tdvmm"):
        e, c, k = x.shape
        e2, k2, n = w.shape
        assert e == e2 and k == k2, (x.shape, w.shape)
        noisy = cfg.noise and key is not None
        code_dtype = _plan_code_dtype(cfg, k, noisy)
        from repro.kernels.tdvmm import ops
        kp = ops.plan_kernel(cfg.backend, c, k, n, code_dtype)

        qx = quant.encode_input(x, cfg.bits)                       # scale (E, C, 1)
        qw = quant.program_weights(w, cfg.weight_bits, cfg.per_channel)
        if noisy:
            qw = quant.program_noise(qw, cfg.spec, key)

        gain = _latch_gain(qx.levels, qw.levels, k)
        # qw.scale is (E, 1, N) per-channel or (E, 1, 1) per-tensor; the explicit
        # last dim (not -1) keeps E=0 expert stacks reshapeable.
        w_scale = jnp.broadcast_to(
            qw.scale.reshape(e, qw.scale.shape[-1]) * (2.0 * k), (e, n))
        out_bits, out_scale = _readout_args(cfg, n_experts=e)
        out_scale, out_window = _runtime_override(cfg, out_bits, out_scale)
        # Per-expert windows: each expert is its own analog tile, so the
        # recorded vector is the (E,) per-tile max the epilogue calibrates.
        _record_window(cfg, qx.view(), qw.view(), kp.backend, code_dtype, gain,
                       per_tile=True)
        y = ops.tdvmm_matmul(
            qx.view(),
            qw.view(),
            qx.scale.reshape(e, c),
            w_scale,
            gain=gain,
            out_bits=out_bits,
            out_scale=out_scale,
            backend=kp.backend,
            code_dtype=code_dtype,
            block_sizes=kp.blocks,
            out_window=out_window,
        )
        return y.astype(x.dtype)


def td_grouped_matmul(
    x: jax.Array,                       # (..., N_in) shared input
    ws: "tuple[jax.Array, ...]",        # G matrices (N_in, N_g), uneven N ok
    cfg: TDVMMLayerConfig,
    key: Optional[jax.Array] = None,
) -> tuple[jax.Array, ...]:
    """Grouped four-quadrant TD-VMM: G same-input projections, one launch.

    The paper's NxN multiplier amortizes its I/O conversion circuitry across
    the whole tile — one DAC encode feeds every output column.  Call sites
    that project the *same* activation through several matrices (attention
    q/k/v, the SSM z/x/B/C/dt input projection) are the model-level analog:
    this encodes ``x`` once and runs the G weight matrices as a single
    **ragged concat** launch — the members concatenate along N into one 2-D
    ``(K, sum N_g)`` bank, each member rounded only to the 128 lane instead
    of padded to the widest member (the old batched-grid stacking cost
    attn.qkv with small KV heads a 2.3x padded-N overhead).

    Padding is exact — zero codes integrate zero charge; per-member
    per-channel weight scales concatenate into the epilogue's per-column
    scale row, and per-member readout windows resolve by column span
    (``group_widths``), so a grouped launch is bit-for-bit identical to the
    G sequential calls whenever the readout windows match (data calibration
    computes a per-member-span window, which *is* the per-call window).
    Returns a tuple of G arrays shaped ``(..., N_g)``.
    """
    ws = tuple(ws)
    if not ws:
        return ()
    if not cfg.enabled:
        return tuple(jnp.dot(x, w) for w in ws)

    with scope("tdvmm"):
        k = x.shape[-1]
        ns = tuple(w.shape[-1] for w in ws)
        for w in ws:
            assert w.ndim == 2 and w.shape[0] == k, (x.shape, w.shape)
        batch_shape = tuple(x.shape[:-1])
        m = 1
        for d in batch_shape:
            m *= d
        noisy = cfg.noise and key is not None
        code_dtype = _plan_code_dtype(cfg, k, noisy)
        from repro.kernels.tdvmm import ops, tdvmm
        # Per-member column spans: each member rounds to the 128 lane only.
        widths = tuple(
            tdvmm.padded_size(n, tdvmm.LANE, tdvmm.LANE) for n in ns)
        n_total = sum(widths)
        kp = ops.plan_kernel(cfg.backend, m, k, n_total, code_dtype)
        # No N block may span two members' readout windows: shrink block_n to
        # the gcd of the plan's choice and every member span (all multiples of
        # the 128 lane, so the gcd stays lane-aligned).
        bn_g = math.gcd(kp.bn, *widths)

        qx = quant.encode_input(x, cfg.bits)                       # encode ONCE
        qw = quant.concat_group(
            [quant.program_weights(w, cfg.weight_bits, cfg.per_channel)
             for w in ws], widths)
        if noisy:
            qw = quant.program_noise(qw, cfg.spec, key)

        gain = _latch_gain(qx.levels, qw.levels, k)
        w_scale = qw.scale.reshape(n_total) * (2.0 * k)
        out_bits, out_scale = _readout_args(cfg, n_experts=len(ws))
        out_scale, out_window = _runtime_override(cfg, out_bits, out_scale)
        # Per-member windows: each member's column span is its own analog tile,
        # so calibration records one (G,) vector for the site.
        _record_window(cfg, qx.view().reshape(m, k), qw.view(), kp.backend,
                       code_dtype, gain, per_tile=True, group_widths=widths)
        y = ops.tdvmm_matmul(
            qx.view().reshape(m, k),
            qw.view(),
            qx.scale.reshape(m),
            w_scale,
            gain=gain,
            out_bits=out_bits,
            out_scale=out_scale,
            backend=kp.backend,
            code_dtype=code_dtype,
            block_sizes=(kp.bm, kp.bk, bn_g),
            group_widths=widths,
            out_window=out_window,
        )                                                          # (M, n_total)
        outs, off = [], 0
        for n, wd in zip(ns, widths):
            outs.append(
                y[:, off:off + n].reshape(batch_shape + (n,)).astype(x.dtype))
            off += wd
        return tuple(outs)


def calibrate_out_scale(
    x: jax.Array, w: jax.Array, cfg: TDVMMLayerConfig,
    key: Optional[jax.Array] = None,
) -> float:
    """Serving-path readout calibration: capture the ADC window once.

    Runs encode -> program -> integrate on a representative batch and returns
    max|z| of the latch-normalized accumulation (the §3.1 output-window
    calibration) as a Python float.  Store it on the config
    (``cfg.replace(out_scale=...)``): per-call windows stop recomputing a
    global max, and the Pallas backend's fused-epilogue kernel becomes
    eligible (a fixed window is tile-local; a data-calibrated one is not).

    ``key`` matters when ``cfg.noise`` is set: the serving path perturbs the
    programmed currents, so the window must be captured over the *noisy*
    codes (``td_matmul`` with the same cfg/key) — a noise-free window would
    underestimate max|z| and clip the noisy deploy outputs.
    """
    if not cfg.enabled:
        raise ValueError("calibrate_out_scale needs an enabled TD-VMM config")
    noisy = cfg.noise and key is not None
    plan = plan_matmul(x.shape, w.shape, cfg, noisy=noisy)
    qx = quant.encode_input(x, cfg.bits)
    qw = quant.program_weights(w, cfg.weight_bits, cfg.per_channel)
    if noisy:
        qw = quant.program_noise(qw, cfg.spec, key)
    from repro.kernels.tdvmm import ops
    acc = ops.codes_matmul(
        qx.view().reshape(plan.m, plan.k), qw.view(), plan.backend,
        code_dtype=plan.code_dtype)
    gain = _latch_gain(qx.levels, qw.levels, plan.k)
    z_max = jnp.max(jnp.abs(acc.astype(jnp.float32) * gain), initial=0.0)
    return max(float(z_max), 1e-9)


def init_linear(
    key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32, scale: float | None = None
) -> jax.Array:
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


class TDVMMLinear:
    """Functional linear layer: params = {'w': (d_in,d_out) [, 'b': (d_out,)]}"""

    @staticmethod
    def init(key, d_in: int, d_out: int, bias: bool = False, dtype=jnp.float32):
        p = {"w": init_linear(key, d_in, d_out, dtype)}
        if bias:
            p["b"] = jnp.zeros((d_out,), dtype)
        return p

    @staticmethod
    def apply(params, x, cfg: TDVMMLayerConfig, key=None):
        y = td_matmul(x, params["w"], cfg, key)
        if "b" in params:
            y = y + params["b"]
        return y

    @staticmethod
    def calibrate(params, x, cfg: TDVMMLayerConfig,
                  key=None) -> TDVMMLayerConfig:
        """Capture the readout window on a representative batch and return a
        config whose ``out_scale`` pins it (serving-path calibration cache).
        Pass ``key`` on noisy configs so the window covers the perturbed
        currents the serving path will actually integrate."""
        return cfg.replace(
            out_scale=calibrate_out_scale(x, params["w"], cfg, key))
