"""Pallas TPU kernel: TD-VMM quantized matmul (charge-accumulation core).

The analog array integrates charge Q[n] = sum_k I[k,n] * on_time[k] — on TPU
that inner product is the MXU's job.  Blocking: (bm x bk) time-code tiles and
(bk x bn) current-code tiles stream HBM->VMEM; a (bm x bn) accumulator lives
in VMEM scratch across the K grid walk (the K axis is the
'arbitrary'/sequential grid dim), so partial charges never round-trip to HBM
— the digital analogue of the capacitor accumulating charge on-node.

Code dtypes (the paper's signal is a p-bit integer code, Eq. 1-3):

  int8   codes with |code| <= 127 (p <= 7 incl. the default p = 6) stream at
         1 byte/code — a quarter of the f32 bytes — and take the MXU's
         int8 x int8 -> int32 path, so charge accumulation is *exact* for any
         K with |acc| < 2^31 (no 2^24 f32 envelope).
  int4   codes with |code| <= 7 (p <= 3) pack TWO codes per int8 byte in HBM
         (``core.quant.pack_int4``: even K index in the low nibble, odd in
         the high) and unpack in-kernel right before the dot — half the
         int8 stream bytes, same exact int32 accumulation, bit-for-bit
         identical to the int8 path.  Launch geometry (K, bk) runs in
         *packed* units; pass ``unpack4=True``.
  f32    integer-valued float codes (p = 8, or noise-perturbed analog
         currents); exact while |acc| < 2^24.

Fused epilogue: the final K step finishes the (bm, bn) tile *in VMEM* —
latch gain, optional p-bit shared-counter readout (Eq. 3) over a calibrated
window, and the per-row x per-channel digital rescale — so the output hits
HBM exactly once, already in model units.  Readout windows enter as
per-column (E, 1, N) operand vectors, so per-expert, per-group-member
(ragged), and scalar windows all ride the same (1, 1, bn) blocks.  The
window's reciprocal and back-scale are computed outside the kernel by
``readout_factors`` — the one place the epilogue divides — so the kernel
only multiplies, rounds and clips, and matches the XLA epilogue
(``ops._epilogue``) bit for bit whatever each compiler does for division.

Data-calibrated readout (``tdvmm_absmax_kernel``): when the window is
max|z| over a whole slot (an expert tile, or one ragged group member's
column span), no single (bm, bn) tile knows it.  A first launch integrates
and writes only each tile's max|z| (an (8, 128) block per tile, no (M, N)
output); the caller reduces those per slot into windows and runs the fused
kernel with them.  The codes are read twice, but exactly one (M, N) product
reaches HBM.

Batched expert grid: a leading E dimension maps (E, M, K) x (E, K, N) MoE
expert stacks onto grid axis 0 — one analog tile per expert — with per-expert
scale vectors riding along as (1, bm, 1) / (1, 1, bn) blocks.

Shared-input grouped grid: (1, M, K) x (G, K, N) runs the *same* time-code
matrix against G stacked weight tiles — the paper's shared-DAC dataflow (one
input encode amortized across every output column of every tile).  The x (and
x_scale) block index maps pin grid axis 0 to batch 0, so HBM holds exactly
one copy of the codes; each group member still owns its per-channel w_scale
and per-tile readout window via the (G, ...) operands.

Block sizes: ``autotune_blocks``/``autotune_lookup`` consult the per-platform
tables in ``autotune_table.py`` (regenerated by scripts/autotune_tdvmm.py);
misses fall back to a dtype heuristic.  MXU alignment: block dims default to
multiples of 128; the minor-most tile minimums are dtype-dependent (f32
sublane 8, int8 sublane 32, lane 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tdvmm.autotune_table import INTERPRET_TABLE, MOSAIC_TABLE

# Default MXU-aligned block shape; pad_to_blocks() aligns arbitrary model
# shapes to these so the divisibility asserts below never constrain callers.
BM, BK, BN = 128, 512, 128
# Mosaic tiling: sublane (second-to-last dim) minimum is dtype-dependent;
# lane (last dim) is always 128.
LANE = 128
_MIN_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32}


def min_sublane(dtype) -> int:
    return _MIN_SUBLANE.get(jnp.dtype(dtype).name, 8)


# ---------------------------------------------------------------------------
# Block-size autotune tables (storage: autotune_table.py, a generated file)
# ---------------------------------------------------------------------------
def autotune_platform() -> str:
    """Which table governs this process: 'mosaic' on real TPUs (VMEM-budgeted
    MXU tiles), 'interpret' elsewhere (grid-step-count-dominated)."""
    return "mosaic" if jax.default_backend() == "tpu" else "interpret"


def autotune_table(platform: str | None = None) -> dict:
    return MOSAIC_TABLE if (
        platform or autotune_platform()) == "mosaic" else INTERPRET_TABLE


# Historic alias: the table the *current* platform consults (the one
# scripts/autotune_tdvmm.py backfills when run on this host).
AUTOTUNE_TABLE = autotune_table()


def _dtype_name(dtype) -> str:
    if isinstance(dtype, str):
        return {"f32": "float32"}.get(dtype, dtype)
    return jnp.dtype(dtype).name


def _heuristic_blocks(name: str, platform: str) -> tuple[int, int, int]:
    if platform == "interpret":
        # Interpret wall-clock ~ grid steps (each is a Python-level block
        # dispatch): take the largest blocks padded_size can launch.  The
        # int-code K blocks still double per byte-halving so the
        # equal-VMEM-budget relation holds on both platforms.
        if name == "int4":
            return (512, 8192, 1024)
        if name == "int8":
            return (512, 4096, 1024)
        return (512, 2048, 1024)
    if name == "int4":
        return (BM, 4 * BK, BN)
    if name == "int8":
        return (BM, 2 * BK, BN)
    return (BM, BK, BN)


def autotune_lookup(
    m: int, k: int, n: int, dtype=jnp.float32, platform: str | None = None,
) -> tuple[tuple[int, int, int], bool]:
    """((bm, bk, bn), table_hit) for a codes matmul on ``platform``.

    Keys are the unpadded (M, K, N, dtype-name); int4 keys use unpacked K.
    Misses fall back to the per-platform dtype heuristic — callers that care
    about the distinction (``ops.plan_kernel``) surface the miss; callers
    must pad with the *same* blocks they launch with (``pad_to_blocks``
    takes them), so any return value is launchable.
    """
    name = _dtype_name(dtype)
    platform = platform or autotune_platform()
    hit = autotune_table(platform).get((m, k, n, name))
    if hit is not None:
        return hit, True
    return _heuristic_blocks(name, platform), False


def autotune_blocks(m: int, k: int, n: int, dtype=jnp.float32) -> tuple[int, int, int]:
    """(bm, bk, bn) for a codes matmul: table hit or dtype heuristic."""
    return autotune_lookup(m, k, n, dtype)[0]


def padded_size(size: int, block: int, tile: int) -> int:
    """Smallest n >= max(size, 1) with n % tile == 0 and n % min(block, n) == 0.

    Rounding to ``tile`` first keeps sub-block dims Mosaic-lowerable on real
    TPUs (block sizes are tile multiples, so block-rounding preserves it).
    Empty dims pad up to one tile — all-zero codes, zero charge — so the
    sliced-back result is the correct empty (or zero) array instead of a
    zero-size grid.
    """
    n = ((max(size, 1) + tile - 1) // tile) * tile
    if n >= block:
        n = ((n + block - 1) // block) * block
    return n


def pad_to_blocks(
    x_codes: jax.Array,      # (..., M, K)
    w_codes: jax.Array,      # (..., K, N)
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
) -> tuple[jax.Array, jax.Array]:
    """Zero-pad code matrices up to block multiples (and MXU tile multiples).

    A zero time code contributes zero charge (the source never turns on), so
    padding is exact: slice the kernel output back to [:M, :N] and the result
    is identical to the unpadded product.  Tile minimums are dtype-aware
    (int8 sublane is 32 vs f32's 8); leading batch (expert) dims pass through
    unpadded — the E grid axis has no tiling constraint.  int4-packed codes
    pad in packed units: pass the packed K and packed bk (both halved
    together, so the same arithmetic applies).
    """
    m, k = x_codes.shape[-2], x_codes.shape[-1]
    n = w_codes.shape[-1]
    mp = padded_size(m, bm, min_sublane(x_codes.dtype))
    # K is x's lane (128) and w's sublane (<= 32): LANE covers both.
    kp = padded_size(k, bk, LANE)
    np_ = padded_size(n, bn, LANE)
    zero = ((0, 0),) * (x_codes.ndim - 2)
    if (mp, kp) != (m, k):
        x_codes = jnp.pad(x_codes, zero + ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w_codes = jnp.pad(w_codes, zero + ((0, kp - k), (0, np_ - n)))
    return x_codes, w_codes


def _unpack_nibbles(packed: jax.Array, axis: int) -> jax.Array:
    """In-kernel int4 unpack: an int8 block of packed code pairs -> the int8
    code block, concatenated as [even-K codes, odd-K codes] along ``axis``.

    The halves-concat permutes K within the block, but the *same* permutation
    applies to the x block's lane axis and the w block's sublane axis, and
    integer dot accumulation is order-independent — so the block dot is
    bit-for-bit the unpacked dot.  Arithmetic shifts sign-extend: the low
    nibble recovers via (v << 28) >> 28, the high via (v << 24) >> 28.  The
    shifts run on int32 lanes: Mosaic has no int8 shift.
    """
    v = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(v, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(v, 24), 28)
    return jnp.concatenate([lo, hi], axis=axis).astype(jnp.int8)


def _pin(v: jax.Array, interpret: bool) -> jax.Array:
    """Pin an epilogue association point against XLA rewrites (interpret).

    In interpret mode the kernel body lowers into the surrounding XLA
    program, so the same constant-sinking / strength-reduction rewrites that
    ops._epilogue pins with optimization_barrier apply here too — the gain
    literal sinks through the readout multiply, mul-by-reciprocal
    strength-reduces back to a divide.  Pinning the identical association
    points keeps fused and unfused epilogues bit-for-bit.  Mosaic lowers the
    jaxpr as written (no algebraic simplifier pass), so the barrier is only
    inserted for interpret and the association still matches.
    """
    return jax.lax.optimization_barrier(v) if interpret else v


# ---------------------------------------------------------------------------
# Kernel body (shared by every entry point)
# ---------------------------------------------------------------------------
def readout_factors(window: jax.Array, out_bits: int
                    ) -> tuple[jax.Array, jax.Array]:
    """(1 / window, window / levels) for a p-bit readout over ``window``.

    The only division of the epilogue.  Both the fused kernels and the XLA
    epilogue (``ops._epilogue``) take these factors from here, evaluated by
    XLA outside any kernel, so the readout is the same arithmetic on every
    backend.  Barriers keep XLA from strength-reducing the reciprocal back
    into a divide or sinking constants through it."""
    window = jax.lax.optimization_barrier(window.astype(jnp.float32))
    levels = float((1 << out_bits) - 1)
    inv = jax.lax.optimization_barrier(jnp.float32(1.0) / window)
    back = jax.lax.optimization_barrier(
        window * (np.float32(1.0) / np.float32(levels)))
    return inv, back


def _kernel(*refs, nk: int, acc_dtype, epilogue: str, gain: float,
            out_bits: int | None, unpack4: bool = False,
            interpret: bool = False):
    """One (bm, bn) tile over the K walk; the final K step runs
    ``epilogue``: "raw" (the accumulator), "fused" (gain, optional readout
    and rescale, in model units) or "absmax" (the tile's max|acc * gain|,
    broadcast over an (8, 128) block)."""
    inv_ref = back_ref = None
    if epilogue == "fused" and out_bits is not None:
        x_ref, w_ref, xs_ref, ws_ref, inv_ref, back_ref, o_ref, acc_ref = refs
    elif epilogue == "fused":
        x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb, wb = x_ref[0], w_ref[0]
    if unpack4:
        xb = _unpack_nibbles(xb, axis=-1)
        wb = _unpack_nibbles(wb, axis=-2)
    acc_ref[...] += jnp.dot(xb, wb, preferred_element_type=acc_dtype)

    @pl.when(pl.program_id(3) == nk - 1)
    def _done():
        acc = acc_ref[...]
        if epilogue == "raw":
            o_ref[0] = acc
            return
        z = _pin(acc.astype(jnp.float32) * gain, interpret)
        if epilogue == "absmax":
            # f32 max is exact and order-free, so the caller's per-slot
            # max over tiles equals the unfused global max bit for bit;
            # zero-padded rows/cols contribute |0|, which can't move it.
            t = jnp.max(jnp.max(jnp.abs(z), axis=1, keepdims=True), axis=0,
                        keepdims=True)
            o_ref[0] = jnp.broadcast_to(t, o_ref.shape[1:])
            return
        # Fused epilogue — the (bm, bn) tile is finished in VMEM and written
        # to HBM exactly once.  The expression mirrors ops._epilogue term for
        # term so the fused and unfused paths stay bit-for-bit identical.
        ws_row = ws_ref[0]
        if out_bits is not None:
            # The window's reciprocal and back-scale ride along as per-column
            # (1, 1, bn) blocks of (E, 1, N) operands — grid axis 0 is the
            # expert axis, so each tile reads its own analog tile's window
            # (ragged grouped launches carry per-member columns).  Runtime
            # values + the constant-free post-round chain
            # ``(q * xs) * (ws * back)`` mirror ops._epilogue term for term.
            levels = float((1 << out_bits) - 1)
            z = jnp.round(jnp.clip(z * inv_ref[0], -1.0, 1.0) * levels)
            ws_row = _pin(ws_row * back_ref[0], interpret)
        o_ref[0] = _pin(z * xs_ref[0], interpret) * ws_row


def _grid_call(e, m, k, n, bm, bk, bn, *, acc_dtype, epilogue, gain,
               out_bits, interpret, shared_x=False, unpack4=False):
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    nk = k // bk
    # Shared-input grouped launch: x (and x_scale) carry a single batch entry
    # that every grid-axis-0 tile reads — one code copy in HBM for G tiles.
    xb = (lambda b: 0) if shared_x else (lambda b: b)
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda b, i, j, s: (xb(b), i, s)),
        pl.BlockSpec((1, bk, bn), lambda b, i, j, s: (b, s, j)),
    ]
    if epilogue == "fused":
        in_specs += [
            pl.BlockSpec((1, bm, 1), lambda b, i, j, s: (xb(b), i, 0)),
            pl.BlockSpec((1, 1, bn), lambda b, i, j, s: (b, 0, j)),
        ]
        if out_bits is not None:
            # (E, 1, N) per-column reciprocal-window + back-scale vectors,
            # one (1, 1, bn) block per tile.
            in_specs += [pl.BlockSpec((1, 1, bn), lambda b, i, j, s: (b, 0, j)),
                         pl.BlockSpec((1, 1, bn), lambda b, i, j, s: (b, 0, j))]
    if epilogue == "absmax":
        out_block, out_dtype = (1, 8, LANE), jnp.float32
        out_shape = (e, (m // bm) * 8, (n // bn) * LANE)
    else:
        out_block, out_shape = (1, bm, bn), (e, m, n)
        out_dtype = acc_dtype if epilogue == "raw" else jnp.float32
    return pl.pallas_call(
        functools.partial(
            _kernel, nk=nk, acc_dtype=acc_dtype, epilogue=epilogue,
            gain=gain, out_bits=out_bits, unpack4=unpack4,
            interpret=interpret),
        grid=(e, m // bm, n // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_block, lambda b, i, j, s: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )


def acc_dtype_for(code_dtype) -> jnp.dtype:
    """Accumulator dtype for a code dtype: int codes accumulate on the MXU
    int8 path (exact int32); float codes in f32.  Single source of truth for
    both the Pallas scratch accumulator and the jnp einsum accumulator
    (ops.py) — they must agree or backend parity breaks."""
    if jnp.issubdtype(jnp.dtype(code_dtype), jnp.integer):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "bm", "bk", "bn", "interpret", "unpack4"))
def tdvmm_matmul_kernel(
    x_codes: jax.Array,      # (M, K) or (E, M, K) signed time codes
    w_codes: jax.Array,      # (K, N) or (E, K, N) signed weight codes
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
    interpret: bool = False,
    unpack4: bool = False,
) -> jax.Array:
    """Raw charge accumulation: int8 codes -> int32 acc, f32 codes -> f32 acc.

    2-D inputs run as a single-expert (E=1) batch; 3-D inputs map the leading
    expert dim onto grid axis 0.  A (1, M, K) x (G, K, N) pair runs the
    shared-input grouped grid: one code copy feeds all G tiles.
    ``unpack4=True`` treats the inputs as int4-packed pairs (K and bk in
    packed units) and unpacks in-kernel.
    """
    squeeze = x_codes.ndim == 2 and w_codes.ndim == 2
    if x_codes.ndim == 2:
        x_codes = x_codes[None]
    if w_codes.ndim == 2:
        w_codes = w_codes[None]
    ex, m, k = x_codes.shape
    e, k2, n = w_codes.shape
    assert (ex == e or ex == 1) and k == k2, (x_codes.shape, w_codes.shape)
    out = _grid_call(
        e, m, k, n, bm, bk, bn, acc_dtype=acc_dtype_for(x_codes.dtype),
        epilogue="raw", gain=1.0, out_bits=None,
        interpret=interpret, shared_x=ex == 1 and e > 1, unpack4=unpack4,
    )(x_codes, w_codes)
    return out[0] if squeeze else out


@functools.partial(jax.jit, static_argnames=(
    "gain", "out_bits", "out_scale", "bm", "bk", "bn", "interpret",
    "unpack4"))
def tdvmm_fused_kernel(
    x_codes: jax.Array,      # (E, M, K) signed time codes (int8 or f32);
    #                          (1, M, K) against (G, K, N) weights = shared-x
    w_codes: jax.Array,      # (E, K, N) signed weight codes
    x_scale: jax.Array,      # (E, M, 1) f32 per-row input scales ((1, M, 1)
    #                          in shared-x mode)
    w_scale: jax.Array,      # (E, 1, N) f32 per-channel weight scales
    window: jax.Array | None = None,  # (E, 1, N) f32 per-column readout
    #                          windows (overrides out_scale; ragged grouped
    #                          launches pass their per-member columns here)
    gain: float = 1.0,
    out_bits: int | None = None,
    out_scale: float | tuple[float, ...] | None = None,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
    interpret: bool = False,
    unpack4: bool = False,
) -> jax.Array:
    """Integrate + fused readout epilogue: model-unit f32 (E, M, N) out.

    The latch gain, the optional p-bit readout over the *fixed* window
    (``out_scale`` — a calibration-time capture; a tuple is an (E,)-vector of
    per-expert windows, one per tile on grid axis 0 — or a full per-column
    ``window`` vector), and the per-row x per-channel rescale all run on the
    finished accumulator tile in VMEM; each output tile is written to HBM
    exactly once.  Data-calibrated windows need a cross-tile max: take it
    from ``tdvmm_absmax_kernel`` and pass it here as ``window``.
    """
    assert x_codes.ndim == 3, "fused kernel is batched; add an E=1 axis"
    if out_bits is not None and out_scale is None and window is None:
        raise ValueError("fused readout needs a fixed out_scale window")
    ex, m, k = x_codes.shape
    e, _, n = w_codes.shape
    assert ex == e or ex == 1, (x_codes.shape, w_codes.shape)
    if (window is None and isinstance(out_scale, tuple)
            and len(out_scale) != e):
        raise ValueError(f"per-expert out_scale: {len(out_scale)} windows "
                         f"for E={e} tiles")
    operands = [x_codes, w_codes, x_scale, w_scale]
    if out_bits is not None:
        # The window enters as runtime (E, 1, N) reciprocal + back-scale
        # operands, never baked literals: constant scales invite XLA
        # strength-reduction / constant reassociation that would break
        # bitwise parity with the unfused and per-call paths (see
        # ops._epilogue).
        if window is None:
            if isinstance(out_scale, tuple):
                window = jnp.asarray(out_scale, jnp.float32).reshape(e, 1, 1)
            else:
                window = jnp.full((e, 1, 1), out_scale, jnp.float32)
        operands += readout_factors(
            jnp.broadcast_to(window.astype(jnp.float32), (e, 1, n)), out_bits)
    return _grid_call(
        e, m, k, n, bm, bk, bn, acc_dtype=acc_dtype_for(x_codes.dtype),
        epilogue="fused", gain=gain, out_bits=out_bits,
        interpret=interpret, shared_x=ex == 1 and e > 1, unpack4=unpack4,
    )(*operands)


@functools.partial(jax.jit, static_argnames=(
    "gain", "bm", "bk", "bn", "interpret", "unpack4"))
def tdvmm_absmax_kernel(
    x_codes: jax.Array,      # (E|1, M, K) signed time codes (int8 or f32)
    w_codes: jax.Array,      # (E, K, N) signed weight codes
    gain: float = 1.0,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
    interpret: bool = False,
    unpack4: bool = False,
) -> jax.Array:
    """Per-tile max|acc * gain|: (E, M // bm, N // bn) f32.

    The first half of a data-calibrated readout: the window is a max over a
    whole slot (an expert tile, or a ragged group member's column span),
    which no single grid step sees.  Each tile writes only its own maximum,
    so no (M, N) array reaches HBM here; the caller reduces the tiles of
    each slot and runs ``tdvmm_fused_kernel`` with the resulting windows.
    """
    assert x_codes.ndim == 3, "absmax kernel is batched; add an E=1 axis"
    ex, m, k = x_codes.shape
    e, _, n = w_codes.shape
    assert ex == e or ex == 1, (x_codes.shape, w_codes.shape)
    out = _grid_call(
        e, m, k, n, bm, bk, bn, acc_dtype=acc_dtype_for(x_codes.dtype),
        epilogue="absmax", gain=gain, out_bits=None,
        interpret=interpret, shared_x=ex == 1 and e > 1, unpack4=unpack4,
    )(x_codes, w_codes)
    return out[:, ::8, ::LANE]
