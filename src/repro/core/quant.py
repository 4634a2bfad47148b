"""Unified quantized-code subsystem: the one QuantizedTensor path from
encoding to the TD-VMM kernel.

The paper's multiplier is an *integer-code* machine: p-bit time codes in,
current codes as weights, charge accumulation, p-bit readout.  Every
quantization boundary in the repo routes through this module so that the jnp
reference path, the Pallas kernel, and the event-driven simulator all agree on
what the digital words are.

Stage -> paper mapping (arXiv:1711.10673):

    encode_input      Eq. 2 / section 4.2 — the shared-counter DAC converts a
                      normalized activation into a p-bit rising-edge time code
                      on the grid T0 = T / 2^p (sign = differential wire pair).
    program_weights   sections 2, 4.1 — floating-gate tuning programs each
                      cell's current to one of 2^p_w levels; per-output-column
                      scaling is the "appropriate scaling of VMM weights" of
                      section 3.1.
    (integrate)       Eq. 1 — charge accumulation; lives in kernels/tdvmm
                      (Pallas on TPU / interpret elsewhere) or jnp.dot.
    readout           Eq. 3 / section 4.2 — the comparator-latch + shared
                      counter reads the crossing time back out as a p-bit code
                      over a calibrated output window.

Code storage: codes with |code| <= 127 (p <= 7, including the default p = 6)
are stored as **int8** — the canonical digital word of the paper's machine.
int8 codes stream from HBM at a quarter of the f32 bytes and take the MXU's
int8 x int8 -> int32 path, where charge accumulation is *exact* for any K
with |acc| < 2^31 (no 2^24 f32 envelope).  p = 8 codes (|code| <= 255) and
noise-perturbed analog currents don't fit int8 and fall back to
integer-valued float32 storage (exact while |acc| < 2^24 — e.g. 6-bit codes
up to K = 4096).

QAT still works on int8 storage: ``QuantizedTensor.view()`` returns the f32
straight-through-estimator view (forward = the stored codes, backward =
identity via the retained linear term), which is what ``dequantize`` and the
kernel's gradient path consume.  Every quantizer is STE-wrapped, so models
stay trainable (standard QAT) no matter which backend integrates.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import encoding as enc
from repro.runtime.trace import scope

# Signed-magnitude codes span [-(2^p - 1), 2^p - 1]: int8 holds p <= 7.
INT8_MAX_BITS = 7
# A signed nibble holds [-8, 7] ⊇ [-7, 7]: p <= 3 packs two codes per byte.
INT4_MAX_BITS = 3


def storage_dtype(bits: int):
    """Canonical code storage: int8 when the signed code range fits."""
    return jnp.int8 if bits <= INT8_MAX_BITS else jnp.float32


def pack_int4(codes: jax.Array, axis: int) -> jax.Array:
    """Pack int8 codes with |code| <= 7 (p <= 3) two-per-byte along ``axis``.

    Byte ``kp`` holds code ``2*kp`` in the low nibble and ``2*kp + 1`` in the
    high nibble.  An odd-length axis is zero-padded to even first — a zero
    code is an inert (never-on) current source, so the pad contributes no
    charge and the unpacked tail column multiplies to exactly zero.  The
    result is an int8 array of half the (even-padded) extent: the HBM word
    the Pallas kernel streams and unpacks in-VMEM (``tdvmm._unpack_nibbles``).
    """
    axis = axis % codes.ndim
    k = codes.shape[axis]
    if k % 2:
        pad = [(0, 0)] * codes.ndim
        pad[axis] = (0, 1)
        codes = jnp.pad(codes, pad)
    codes = codes.astype(jnp.int8)
    idx_lo = [slice(None)] * codes.ndim
    idx_hi = [slice(None)] * codes.ndim
    idx_lo[axis] = slice(0, None, 2)
    idx_hi[axis] = slice(1, None, 2)
    lo = codes[tuple(idx_lo)]
    hi = codes[tuple(idx_hi)]
    return (lo & jnp.int8(0x0F)) | (hi << 4).astype(jnp.int8)


def unpack_int4(packed: jax.Array, k: int, axis: int) -> jax.Array:
    """Inverse of ``pack_int4``: int8 nibble pairs -> ``k`` int8 codes.

    Arithmetic shifts sign-extend the nibbles ((v << 4) >> 4 for the low,
    v >> 4 for the high), then the even/odd columns interleave back along
    ``axis``; a pad column from an odd ``k`` is dropped.
    """
    axis = axis % packed.ndim
    packed = packed.astype(jnp.int8)
    lo = ((packed << 4).astype(jnp.int8) >> 4).astype(jnp.int8)
    hi = (packed >> 4).astype(jnp.int8)
    out = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(packed.shape)
    shape[axis] = 2 * packed.shape[axis]
    out = out.reshape(shape)
    idx = [slice(None)] * out.ndim
    idx[axis] = slice(0, k)
    return out[tuple(idx)]


def ste(x_quant: jax.Array, x: jax.Array) -> jax.Array:
    """Straight-through estimator: forward ``x_quant``, backward identity."""
    return x + jax.lax.stop_gradient(x_quant - x)


def signed_codes(x: jax.Array, bits: int) -> jax.Array:
    """Value in [-1, 1] -> integer-valued signed code in [-L, L], L = 2^p - 1.

    The sign folds the differential (+/-) wire pair of the four-quadrant
    multiplier.  STE in the code domain: forward is the rounded code, backward
    is d(code)/d(x) = L, so dequantizing (code * scale / L) has identity
    gradient in the value domain — exactly the seed fake-quant STE.
    """
    levels = float((1 << bits) - 1)
    q = enc.quantize_code_signed(x, bits).astype(jnp.float32)
    return ste(q, x * levels)


@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """Integer codes + the scale that maps them back to model units.

    codes:  int8 in [-levels, levels] when p <= 7 (the canonical storage —
            quarter of the f32 HBM bytes, feeds the kernel's exact int32
            accumulation path), else f32.  f32 codes are STE-wrapped and
            directly differentiable in the QAT sense; they may also be
            non-integer (programming noise models analog current
            perturbation) and are still valid kernel input.
    scale:  f32, broadcastable against the dequantized value — per-row
            ``(..., 1)`` for activations, per-channel ``(1, N)`` or per-tensor
            ``(1, 1)`` for weights.  Always stop-gradient.
    bits:   static code width p.
    ste:    optional f32 linear term (the unrounded ``x * levels``) retained
            for QAT alongside int8 storage; ``view()`` splices it into a
            straight-through estimator.  None on serving paths (and dead
            code the compiler drops whenever gradients aren't taken).
    """

    codes: jax.Array
    scale: jax.Array
    bits: int
    ste: Optional[jax.Array] = None

    @property
    def levels(self) -> int:
        return (1 << self.bits) - 1

    def view(self) -> jax.Array:
        """f32 STE view of the codes: forward = stored codes, backward =
        identity (through ``ste`` when present).  This is what the compute
        and gradient paths consume; ``codes`` itself is the storage word."""
        if jnp.issubdtype(self.codes.dtype, jnp.floating):
            return self.codes          # f32 codes already carry their STE
        qf = self.codes.astype(jnp.float32)
        if self.ste is None:
            return qf
        # qf + (ste - sg(ste)), not ste + sg(qf - ste): the correction term
        # is exactly +0.0 in IEEE arithmetic, so the forward value is the
        # *integer* code — float summation over integer products is then
        # order-independent, which is what keeps ragged/blocked launches
        # bit-for-bit with their sequential counterparts even under QAT.
        # The old form rounds twice and lands an ulp off the code grid.
        return qf + (self.ste - jax.lax.stop_gradient(self.ste))

    def dequantize(self) -> jax.Array:
        """Back to model units: codes / L * scale."""
        return self.view() * (self.scale / float(self.levels))


jax.tree_util.register_dataclass(
    QuantizedTensor, data_fields=["codes", "scale", "ste"],
    meta_fields=["bits"])


def _store(normalized: jax.Array, bits: int) -> tuple[jax.Array, Optional[jax.Array]]:
    """(codes, ste) for a normalized value in [-1, 1]: int8 storage + retained
    f32 linear term when the code range fits int8, else STE-wrapped f32
    (``signed_codes`` — the single source of the STE convention)."""
    if storage_dtype(bits) == jnp.int8:
        lin = normalized * float((1 << bits) - 1)
        return enc.quantize_code_signed(normalized, bits).astype(jnp.int8), lin
    return signed_codes(normalized, bits), None


def encode_input(x: jax.Array, bits: int, axis: int = -1) -> QuantizedTensor:
    """Input stage (Eq. 2): per-row range normalization + p-bit time codes.

    The scale is the per-example input range max|x| along ``axis`` (the analog
    front-end normalizes each sample into the [0, T] window); it is
    stop-gradient, matching the seed layer.
    """
    xf = x.astype(jnp.float32)
    # initial=0.0 is an identity for |x| maxes and keeps zero-size batches
    # (e.g. a serving batch filtered to nothing) from hitting the no-identity
    # reduction error; the 1e-6 clamp then supplies the scale.
    s = jax.lax.stop_gradient(jnp.maximum(
        jnp.max(jnp.abs(xf), axis=axis, keepdims=True, initial=0.0), 1e-6))
    codes, lin = _store(xf / s, bits)
    return QuantizedTensor(codes=codes, scale=s, bits=bits, ste=lin)


@scope("weight_program")
def program_weights(
    w: jax.Array, bits: int, per_channel: bool = True
) -> QuantizedTensor:
    """Weight stage (sections 2, 4.1): FG current codes + column scaling.

    ``per_channel`` scales each output column independently (the N_in axis of
    a (N_in, N_out) matrix — axis -2, so stacked (E, N_in, N_out) expert
    banks get per-expert-per-column scales); otherwise one scale per weight
    tile (per expert for stacked banks).
    """
    wf = w.astype(jnp.float32)
    axes = (-2,) if per_channel else (-2, -1)
    w_max = jax.lax.stop_gradient(jnp.maximum(
        jnp.max(jnp.abs(wf), axis=axes, keepdims=True, initial=0.0), 1e-6))
    # No explicit clip: the stored code already clips to the code range, and
    # the STE linear term must stay unclipped — a clip here would halve
    # the gradient of every per-channel max-magnitude weight (the clip
    # boundary is a min/max tie at exactly |w| == w_max).
    codes, lin = _store(wf / w_max, bits)
    return QuantizedTensor(codes=codes, scale=w_max, bits=bits, ste=lin)


@scope("weight_program")
def stack_group(qws: "list[QuantizedTensor] | tuple[QuantizedTensor, ...]",
                n_to: int) -> QuantizedTensor:
    """Stack G programmed (K, N_g) weight members into one (G, K, n_to) bank.

    The grouped TD-VMM launch (``core.layers.td_grouped_matmul``) runs one
    shared input against G same-input projection matrices; uneven output
    widths are zero-padded up to ``n_to`` (the group's block-rounded max-N).
    Zero codes are inert — a never-on current source — so padded columns
    integrate zero charge and their sliced-off outputs are exactly zero.
    Padded scale entries are 1.0 (never multiplied against a nonzero code).

    Members must share the code width; per-channel ``(1, N_g)`` and
    per-tensor ``(1, 1)`` scales both stack to a ``(G, 1, n_to)`` scale.  STE
    linear terms stack alongside the codes (zero-padded — identity gradient
    through a zero pad is still zero).
    """
    if not qws:
        raise ValueError("stack_group needs at least one member")
    bits = qws[0].bits
    if any(q.bits != bits for q in qws):
        raise ValueError(
            f"grouped members must share a code width, got "
            f"{[q.bits for q in qws]}")
    if any(q.codes.ndim != 2 for q in qws):
        raise ValueError("stack_group stacks 2-D (K, N) weight members")
    if any(q.codes.shape[-1] > n_to for q in qws):
        raise ValueError(
            f"n_to={n_to} smaller than a member width "
            f"{[q.codes.shape[-1] for q in qws]}")

    def pad_codes(c):
        return jnp.pad(c, ((0, 0), (0, n_to - c.shape[-1])))

    codes = jnp.stack([pad_codes(q.codes) for q in qws])
    scale = jnp.stack([jnp.pad(
        jnp.broadcast_to(q.scale, (1, q.codes.shape[-1])),
        ((0, 0), (0, n_to - q.codes.shape[-1])), constant_values=1.0)
        for q in qws])
    stes = None
    if all(q.ste is not None for q in qws):
        stes = jnp.stack([pad_codes(q.ste) for q in qws])
    return QuantizedTensor(codes=codes, scale=scale, bits=bits, ste=stes)


@scope("weight_program")
def concat_group(qws: "list[QuantizedTensor] | tuple[QuantizedTensor, ...]",
                 widths: "tuple[int, ...]") -> QuantizedTensor:
    """Concatenate G programmed (K, N_g) members along N into one ragged bank.

    The ragged grouped TD-VMM launch (``core.layers.td_grouped_matmul``) runs
    one shared input against the column concat of G same-input projections —
    a single 2-D (K, sum widths) launch in which member g owns the
    ``widths[g]``-wide column span.  Each member zero-pads only up to its own
    ``widths[g]`` (its lane-rounded width), NOT to the widest member — that
    per-member rounding is the whole point versus ``stack_group``'s
    (G, K, max-N) batched bank under uneven widths (heavy GQA).  Zero codes
    are inert, so pad columns integrate zero charge; padded scale entries are
    1.0 (never multiplied against a nonzero code).  STE linear terms concat
    alongside the codes.
    """
    if not qws:
        raise ValueError("concat_group needs at least one member")
    if len(widths) != len(qws):
        raise ValueError(f"{len(widths)} widths for {len(qws)} members")
    bits = qws[0].bits
    if any(q.bits != bits for q in qws):
        raise ValueError(
            f"grouped members must share a code width, got "
            f"{[q.bits for q in qws]}")
    if any(q.codes.ndim != 2 for q in qws):
        raise ValueError("concat_group concatenates 2-D (K, N) members")
    if any(q.codes.shape[-1] > wd for q, wd in zip(qws, widths)):
        raise ValueError(
            f"member widths {[q.codes.shape[-1] for q in qws]} exceed the "
            f"declared spans {tuple(widths)}")

    def pad_codes(c, wd):
        return jnp.pad(c, ((0, 0), (0, wd - c.shape[-1])))

    codes = jnp.concatenate(
        [pad_codes(q.codes, wd) for q, wd in zip(qws, widths)], axis=-1)
    scale = jnp.concatenate(
        [jnp.pad(jnp.broadcast_to(q.scale, (1, q.codes.shape[-1])),
                 ((0, 0), (0, wd - q.codes.shape[-1])), constant_values=1.0)
         for q, wd in zip(qws, widths)], axis=-1)
    stes = None
    if all(q.ste is not None for q in qws):
        stes = jnp.concatenate(
            [pad_codes(q.ste, wd) for q, wd in zip(qws, widths)], axis=-1)
    return QuantizedTensor(codes=codes, scale=scale, bits=bits, ste=stes)


@scope("weight_program")
def program_noise(qw: QuantizedTensor, spec, key: jax.Array) -> QuantizedTensor:
    """Stochastic DIBL + FG tuning noise on programmed current codes.

    Multiplicative, so it is identical in the code and value domains; the
    perturbed codes are intentionally non-integer (analog currents), so the
    result always carries f32 codes — int8 storage (and the kernel's int
    path) is for noise-free digital words only.
    """
    from repro.core import nonideal

    err = nonideal.relative_error(
        spec.i_max, jnp.asarray(spec.v_sg), jnp.asarray(spec.delta_vd))
    k1, k2 = jax.random.split(key)
    view = qw.view()
    # Explicit f32 draws: the code pipeline is f32 end-to-end, independent of
    # the process-wide jax_enable_x64 flag (which would silently promote the
    # perturbed codes to f64).
    u = jax.random.uniform(
        k1, view.shape, jnp.float32, minval=-1.0, maxval=1.0)
    codes = view * (1.0 + err.astype(jnp.float32) * u)
    codes = codes * jnp.exp(
        0.003 * jax.random.normal(k2, view.shape, jnp.float32))
    return QuantizedTensor(codes=codes, scale=qw.scale, bits=qw.bits)


def readout(
    y: jax.Array, bits: int, scale: jax.Array | float | None = None
) -> jax.Array:
    """Readout stage (Eq. 3 / section 4.2): p-bit ADC over the output window.

    ``scale=None`` calibrates the window to max|y| (stop-gradient) — the
    section-3.1 weight-scaling calibration that fills [T, 2T] before the
    shared-counter ADC samples it.  Pass an explicit ``scale`` for a fixed
    window (e.g. 0.5 for the raw differential range of a normalized tile).
    Forward is the quantized value, backward identity (STE).
    """
    if scale is None:
        scale = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(y), initial=0.0), 1e-9))
    levels = float((1 << bits) - 1)
    return signed_codes(y / scale, bits) * (scale / levels)
