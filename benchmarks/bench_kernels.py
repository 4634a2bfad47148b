"""Kernel micro-benchmarks: Pallas (interpret mode on CPU — correctness-path
timing only; Mosaic compilation happens on real TPUs) vs the jnp reference
path, plus the arithmetic-intensity accounting that motivates each kernel.

Emits ``BENCH_kernels.json`` (bytes moved, GB/s, us per shape, op counts,
jnp-vs-pallas speedups) so CI tracks the perf trajectory run over run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.common import emit, reset_rows, save_json, time_call
from repro.core.layers import TDVMMLayerConfig, td_grouped_matmul, td_matmul
from repro.kernels.crossing.ref import crossing_ref
from repro.kernels.ssd.ref import ssd_naive
from repro.kernels.tdvmm.ops import tdvmm_matmul
from repro.kernels.tdvmm.ref import tdvmm_matmul_ref
from repro.models.ssm import ssd_chunked


def _codes(key, shape, dtype):
    c = jnp.round(jax.random.uniform(key, shape, minval=-63, maxval=63))
    return c.astype(dtype)


def bench_tdvmm_backends():
    """jnp vs Pallas parity + throughput at model shapes.

    On CPU the Pallas path runs in interpret mode (Python-level grid walk):
    the numbers quantify interpret overhead, not TPU performance — the point
    of the row pair is the parity column (max |jnp - pallas|, must be 0) and
    the jnp-path GFLOP/s at shapes a model actually emits.
    """
    from repro.kernels.tdvmm import ops as tdops
    for (m, k, n) in [(512, 1024, 4096), (256, 896, 896), (33, 300, 130)]:
        kx, kw = jax.random.split(jax.random.PRNGKey(m + n))
        xc = _codes(kx, (m, k), jnp.float32)
        wc = _codes(kw, (k, n), jnp.float32)
        xs = jnp.ones((m,))
        ws = jnp.ones((n,))
        flops = 2 * m * k * n
        outs = {}
        for backend in ("jnp", "pallas"):
            # Plan through plan_kernel so each row records the chosen blocks
            # and whether the autotune table answered (miss = heuristic
            # fallback, visible here instead of quietly slow).
            kp = tdops.plan_kernel(backend, m, k, n, "f32")
            fn = jax.jit(functools.partial(
                tdvmm_matmul, gain=1e-4, out_bits=6, backend=backend,
                block_sizes=kp.blocks))
            outs[backend] = fn(xc, wc, xs, ws)
            us = time_call(fn, xc, wc, xs, ws, iters=3)
            emit(f"tdvmm_{backend}_{m}x{k}x{n}", us,
                 f"GFLOP/s={flops/us*1e-3:.1f}|blocks={kp.blocks}"
                 f"|hit={kp.autotune_hit}",
                 data={"m": m, "k": k, "n": n,
                       "gflops_per_s": round(flops / us * 1e-3, 1),
                       "plan_blocks": list(kp.blocks),
                       "autotune_hit": kp.autotune_hit,
                       "autotune_platform": kp.platform})
        parity = float(jnp.max(jnp.abs(outs["jnp"] - outs["pallas"])))
        emit(f"tdvmm_parity_{m}x{k}x{n}", 0.0, f"max_abs_diff={parity}",
             data={"max_abs_diff": parity})

    # full layer path (encode -> integrate -> readout -> rescale)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 1024))
    w = jax.random.normal(jax.random.PRNGKey(2), (1024, 4096)) * 0.05
    for backend in ("jnp", "pallas"):
        cfg = TDVMMLayerConfig(enabled=True, backend=backend)
        fn = jax.jit(lambda x, w, cfg=cfg: td_matmul(x, w, cfg))
        us = time_call(fn, x, w, iters=3)
        emit(f"td_matmul_layer_{backend}_256x1024x4096", us,
             f"GFLOP/s={2*256*1024*4096/us*1e-3:.1f}")


def _iter_eqns(fn, args):
    """Every equation in the traced program of fn(*args), recursing into
    nested (pjit/scan/pallas) sub-jaxprs — one traversal shared by all the
    jaxpr-derived bench metrics."""
    eqns = []

    def walk(jx):
        for eqn in jx.eqns:
            eqns.append(eqn)
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else [val]):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return eqns


def _matmul_operand_dtype(fn, args):
    """The dtype actually reaching the codes matmul: the first contraction
    (dot_general) in the traced program, by its LHS input dtype.  This keeps
    the bytes-moved claim honest — if the int8 dispatch ever regressed to
    f32, this (and the CI invariant built on it) would catch it, not just
    the analytic itemsize arithmetic."""
    for eqn in _iter_eqns(fn, args):
        if eqn.primitive.name == "dot_general":
            return str(eqn.invars[0].aval.dtype)
    return "none"


def bench_int8_vs_f32_codes():
    """The headline bytes-moved win: int8 code storage streams the codes
    matmul at a quarter of the f32 HBM bytes (and accumulates exactly in
    int32, so there is no 2^24 envelope to respect).

    ``bytes_hbm`` is the analytic HBM traffic of the codes matmul — code
    reads + one f32 output write — cross-checked against the dtype the
    traced dot_general actually consumes (``matmul_operand_dtype``); CPU
    wall time is reported for trajectory tracking but XLA-CPU's int8 matmul
    codegen is not the serving target.
    """
    byte_rows, op_dtypes = {}, {}
    for (m, k, n) in [(512, 2048, 512), (512, 1024, 4096)]:
        kx, kw = jax.random.split(jax.random.PRNGKey(k))
        for name, dt in (("int8", jnp.int8), ("f32", jnp.float32)):
            xc = _codes(kx, (m, k), dt)
            wc = _codes(kw, (k, n), dt)
            xs = jnp.ones((m,))
            ws = jnp.ones((n,))
            itemsize = jnp.dtype(dt).itemsize
            bytes_hbm = (m * k + k * n) * itemsize + m * n * 4
            fn = jax.jit(functools.partial(
                tdvmm_matmul, gain=1e-4, out_bits=6, out_scale=0.5,
                backend="jnp"))
            us = time_call(fn, xc, wc, xs, ws, iters=3)
            byte_rows[(m, k, n, name)] = bytes_hbm
            op_dtypes[(m, k, n, name)] = _matmul_operand_dtype(
                fn, (xc, wc, xs, ws))
            emit(f"tdvmm_codes_{name}_{m}x{k}x{n}", us,
                 f"HBM_MB={bytes_hbm/2**20:.2f}|GB/s={bytes_hbm/us*1e-3:.2f}",
                 data={"m": m, "k": k, "n": n, "code_dtype": name,
                       "matmul_operand_dtype": op_dtypes[(m, k, n, name)],
                       "bytes_hbm": bytes_hbm,
                       "gb_per_s": round(bytes_hbm / us * 1e-3, 2)})
        ratio = byte_rows[(m, k, n, "f32")] / byte_rows[(m, k, n, "int8")]
        int8_verified = op_dtypes[(m, k, n, "int8")] == "int8"
        emit(f"tdvmm_codes_bytes_ratio_{m}x{k}x{n}", 0.0,
             f"f32_bytes/int8_bytes={ratio:.2f}x|int8_dot={int8_verified}",
             data={"bytes_reduction": round(ratio, 2),
                   "int8_reduces_hbm_bytes": ratio > 1.0 and int8_verified})


def _pallas_input_bytes(fn, args):
    """Total bytes of the first pallas_call's operands in the traced program
    — the actual HBM->VMEM stream footprint of the kernel launch, which is
    how the int4 packing claim is verified (the packed launch must stream
    about half the int8 code bytes, not just claim to)."""
    for eqn in _iter_eqns(fn, args):
        if eqn.primitive.name == "pallas_call":
            total = 0
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "shape", None) is not None:
                    size = 1
                    for d in aval.shape:
                        size *= d
                    total += size * jnp.dtype(aval.dtype).itemsize
            return total
    return 0


def bench_int4_packing():
    """int4 code packing (p <= 3): two codes per byte in the HBM stream.

    The Pallas launch consumes nibble-packed int8 arrays (K in packed units)
    and unpacks in-VMEM right before the dot — the analytic code-byte ratio
    vs int8 is 0.5, cross-checked against the traced pallas_call's actual
    operand bytes, and the outputs must be bit-for-bit identical to int8
    (same int32 accumulation, order-independent).
    """
    for (m, k, n) in [(512, 2048, 512), (512, 1024, 4096)]:
        kx, kw = jax.random.split(jax.random.PRNGKey(k + 1))
        xc = jnp.round(jax.random.uniform(
            kx, (m, k), minval=-7, maxval=7)).astype(jnp.int8)
        wc = jnp.round(jax.random.uniform(
            kw, (k, n), minval=-7, maxval=7)).astype(jnp.int8)
        xs = jnp.ones((m,))
        ws = jnp.ones((n,))
        outs, code_bytes, stream_bytes = {}, {}, {}
        for name in ("int8", "int4"):
            fn = jax.jit(functools.partial(
                tdvmm_matmul, gain=1e-4, out_bits=6, out_scale=0.5,
                backend="pallas", code_dtype=name))
            outs[name] = fn(xc, wc, xs, ws)
            kb = (k + 1) // 2 if name == "int4" else k
            code_bytes[name] = m * kb + kb * n
            stream_bytes[name] = _pallas_input_bytes(fn, (xc, wc, xs, ws))
            us = time_call(fn, xc, wc, xs, ws, iters=3)
            emit(f"tdvmm_codes_{name}_pallas_{m}x{k}x{n}", us,
                 f"code_MB={code_bytes[name]/2**20:.2f}",
                 data={"m": m, "k": k, "n": n, "code_dtype": name,
                       "code_bytes": code_bytes[name],
                       "pallas_stream_bytes": stream_bytes[name]})
        parity = float(jnp.max(jnp.abs(outs["int8"] - outs["int4"])))
        ratio = code_bytes["int4"] / code_bytes["int8"]
        # Scale vectors ride along in both launches; <= 0.6 still requires
        # the code operands themselves to have halved.
        streamed = stream_bytes["int4"] <= 0.6 * stream_bytes["int8"]
        emit(f"tdvmm_int4_codes_ratio_{m}x{k}x{n}", 0.0,
             f"int4_bytes/int8_bytes={ratio:.2f}|max_abs_diff={parity}",
             data={"code_bytes_ratio": round(ratio, 3),
                   "max_abs_diff_vs_int8": parity,
                   "packed_stream_verified": streamed,
                   "int4_halves_code_bytes": (
                       ratio <= 0.5 and parity == 0.0 and streamed)})


def _count_launches(fn, args):
    """Codes-matmul dispatches in the traced program: each td_matmul is one
    contraction (a dot_general — inside the pallas_call body on the Pallas
    backend, at the top level on jnp), so the grouped path's 3-to-1 / 5-to-1
    launch collapse shows up directly as the dot_general count."""
    return sum(1 for eqn in _iter_eqns(fn, args)
               if eqn.primitive.name == "dot_general")


def _count_encodes(fn, args, m, k):
    """Input-encode materializations: conversions *producing* an int8 (M, K)
    code matrix in the traced program (view ops like squeeze/reshape over
    already-encoded codes don't count).  The sequential path re-encodes the
    same activation once per projection; the grouped launch encodes once."""
    return sum(
        eqn.primitive.name == "convert_element_type"
        and any(getattr(v.aval, "shape", ()) == (m, k)
                and getattr(v.aval, "dtype", None) == jnp.int8
                for v in eqn.outvars)
        for eqn in _iter_eqns(fn, args))


def bench_grouped_projection():
    """Grouped-projection TD-VMM: attn.qkv (G=3) and ssm.in_proj (G=5) as ONE
    shared-input ragged concat launch vs G sequential td_matmul dispatches.

    The paper's NxN tile amortizes one DAC encode across every output column;
    the grouped launch is the model-level analog — the metrics are the launch
    count (G -> 1), the encode-bytes reduction (the input code matrix is
    materialized once instead of G times), and the grouped-vs-sequential
    parity (bit-for-bit 0.0 under matching per-member windows, both
    backends).  Padded-N overhead reports the zero-code columns the ragged
    concat adds: each member rounds only to the 128 lane (the old batched
    stacking padded every member to the widest — 2.33x on attn.qkv under
    heavy GQA; the ragged grid is ~1.0x).
    """
    from repro.kernels.tdvmm import tdvmm
    cases = {
        "attn_qkv": (64, 896, (896, 128, 128)),          # wq / wk / wv
        "ssm_in_proj": (64, 512, (1024, 1024, 128, 128, 16)),  # z/x/B/C/dt
    }
    for name, (m, k, ns) in cases.items():
        g = len(ns)
        x = jax.random.normal(jax.random.PRNGKey(g), (m, k))
        ws = tuple(jax.random.normal(jax.random.PRNGKey(17 + i), (k, n)) * 0.1
                   for i, n in enumerate(ns))
        outs = {}
        for backend in ("jnp", "pallas"):
            cfg = TDVMMLayerConfig(enabled=True, backend=backend)
            grouped_fn = jax.jit(
                lambda x_, ws_, c=cfg: td_grouped_matmul(x_, ws_, c))
            seq_fn = jax.jit(
                lambda x_, ws_, c=cfg: tuple(td_matmul(x_, w, c) for w in ws_))
            outs[backend] = (grouped_fn(x, ws), seq_fn(x, ws))
            if backend == "jnp":
                launches = {"grouped": _count_launches(grouped_fn, (x, ws)),
                            "sequential": _count_launches(seq_fn, (x, ws))}
                encodes = {"grouped": _count_encodes(grouped_fn, (x, ws), m, k),
                           "sequential": _count_encodes(seq_fn, (x, ws), m, k)}
                us_g = time_call(grouped_fn, x, ws, iters=3)
                us_s = time_call(seq_fn, x, ws, iters=3)
        parity = max(
            float(jnp.max(jnp.abs(a - b)))
            for grouped, seq in outs.values()
            for a, b in zip(grouped, seq))
        cross = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(outs["jnp"][0], outs["pallas"][0]))
        widths = tuple(
            tdvmm.padded_size(nn, tdvmm.LANE, tdvmm.LANE) for nn in ns)
        n_total = sum(widths)
        emit(f"tdvmm_grouped_{name}_jnp", us_g,
             f"sequential_us={us_s:.1f}|launches={launches['grouped']}v"
             f"{launches['sequential']}",
             data={"m": m, "k": k, "ns": list(ns), "cpu_us_grouped": us_g,
                   "cpu_us_sequential": us_s})
        emit(f"tdvmm_grouped_launch_count_{name}", 0.0,
             f"launches {launches['sequential']}->{launches['grouped']}|"
             f"encodes {encodes['sequential']}->{encodes['grouped']}|"
             f"max_abs_diff={parity}",
             data={"group": g,
                   "grouped_launches": launches["grouped"],
                   "sequential_launches": launches["sequential"],
                   "one_launch": (launches["grouped"] == 1
                                  and launches["sequential"] == g),
                   "grouped_encodes": encodes["grouped"],
                   "sequential_encodes": encodes["sequential"],
                   "encode_bytes_reduction": round(
                       encodes["sequential"] / max(encodes["grouped"], 1), 2),
                   "encode_bytes_grouped": encodes["grouped"] * m * k,
                   "encode_bytes_sequential": encodes["sequential"] * m * k,
                   "member_widths": list(widths),
                   "n_total": n_total,
                   "padded_n_overhead": round(n_total / sum(ns), 3),
                   "max_abs_diff_vs_sequential": parity,
                   "max_abs_diff_jnp_vs_pallas": cross})


# Pure view/layout primitives: no HBM materialization of their own.
_VIEW_PRIMS = {"squeeze", "reshape", "broadcast_in_dim", "transpose"}


def _count_mn_hbm_materializations(fn, args, m, n):
    """Count *top-level* jaxpr equations that materialize an (M, N)-shaped
    array — each one is an HBM round-trip of the full output tile before XLA
    fusion (the fused kernel's guarantee is exactly one such write).

    Does NOT recurse into pallas_call bodies: with autotuned interpret
    blocks a kernel-body block can equal the whole (M, N) tile, but block
    values live in VMEM — only the pallas_call's own output is an HBM
    write.  View primitives (squeeze/reshape/...) are excluded for the same
    reason."""
    count = 0

    def walk(jx):
        nonlocal count
        for eqn in jx.eqns:
            if eqn.primitive.name in _VIEW_PRIMS:
                continue
            mn_out = any(getattr(v.aval, "shape", ())[-2:] == (m, n)
                         for v in eqn.outvars)
            if eqn.primitive.name == "pallas_call":
                # The kernel's own output IS the one HBM write; block values
                # inside the body live in VMEM, so don't recurse.
                count += mn_out
                continue
            subs = [sub for val in eqn.params.values()
                    for sub in (val if isinstance(val, (list, tuple))
                                else [val])
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr")]
            if subs:
                # Call-like wrapper (pjit / custom_vjp / scan): not a
                # materialization itself — count what happens inside.
                for sub in subs:
                    walk(sub if hasattr(sub, "eqns") else sub.jaxpr)
                continue
            count += mn_out

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return count


def bench_fused_epilogue():
    """Fused in-kernel epilogue (gain + p-bit readout over a fixed window +
    per-row x per-channel rescale) vs the unfused jnp chain.

    The interpret-measured metric is the count of (M, N) materializations in
    the traced program: the unfused path builds the accumulator and then a
    chain of full-size elementwise intermediates, while the fused kernel
    finishes each tile in VMEM and writes HBM once.  On TPU that is the
    wall-clock difference; on CPU wall time only tracks interpret overhead.
    """
    m, k, n = 256, 1024, 512
    kx, kw = jax.random.split(jax.random.PRNGKey(3))
    xc = _codes(kx, (m, k), jnp.int8)
    wc = _codes(kw, (k, n), jnp.int8)
    xs = jax.random.uniform(jax.random.PRNGKey(4), (m,), minval=0.5, maxval=2.0)
    ws = jax.random.uniform(jax.random.PRNGKey(5), (n,), minval=0.5, maxval=2.0)
    counts, times = {}, {}
    for backend in ("jnp", "pallas"):
        fn = jax.jit(functools.partial(
            tdvmm_matmul, gain=1e-4, out_bits=6, out_scale=0.5,
            backend=backend))
        counts[backend] = _count_mn_hbm_materializations(
            fn, (xc, wc, xs, ws), m, n)
        y = fn(xc, wc, xs, ws)
        jax.block_until_ready(y)
        times[backend] = time_call(fn, xc, wc, xs, ws, iters=3)
        emit(f"tdvmm_epilogue_{backend}_{m}x{k}x{n}", times[backend],
             f"MN_materializations={counts[backend]}",
             data={"m": m, "k": k, "n": n,
                   "mn_materializations": counts[backend],
                   "fused": backend == "pallas"})
    emit(f"tdvmm_fused_epilogue_opcount_{m}x{k}x{n}", 0.0,
         f"unfused_jnp={counts['jnp']}|fused_pallas={counts['pallas']}",
         data={"unfused_mn_ops": counts["jnp"],
               "fused_mn_ops": counts["pallas"],
               "fused_beats_unfused_opcount":
                   counts["pallas"] < counts["jnp"],
               "cpu_us_jnp": round(times["jnp"], 1),
               "cpu_us_pallas_interpret": round(times["pallas"], 1)})

    # Data-calibrated readout (out_scale=None, the output_calibration=True
    # serving path): a per-tile max launch feeds the fused kernel its slot
    # windows — ONE (M, N) HBM write — vs the legacy two-pass path
    # (integrate kernel + unfused jnp epilogue).
    cal_counts, cal_outs = {}, {}
    for mode, fused in (("fused", True), ("unfused", False)):
        fn = jax.jit(functools.partial(
            tdvmm_matmul, gain=1e-4, out_bits=6, backend="pallas",
            fused_calibration=fused))
        cal_outs[mode] = fn(xc, wc, xs, ws)
        cal_counts[mode] = _count_mn_hbm_materializations(
            fn, (xc, wc, xs, ws), m, n)
        cal_counts[f"us_{mode}"] = time_call(fn, xc, wc, xs, ws, iters=3)
    jnp_fn = jax.jit(functools.partial(
        tdvmm_matmul, gain=1e-4, out_bits=6, backend="jnp"))
    cal_outs["jnp"] = jnp_fn(xc, wc, xs, ws)
    parity = float(jnp.max(jnp.abs(cal_outs["fused"] - cal_outs["unfused"])))
    parity_jnp = float(jnp.max(jnp.abs(cal_outs["fused"] - cal_outs["jnp"])))
    emit(f"tdvmm_calibrated_epilogue_{m}x{k}x{n}", cal_counts["us_fused"],
         f"MN_writes fused={cal_counts['fused']} "
         f"unfused={cal_counts['unfused']}|max_abs_diff={parity}",
         data={"m": m, "k": k, "n": n,
               "fused_mn_materializations": cal_counts["fused"],
               "unfused_mn_materializations": cal_counts["unfused"],
               "single_mn_write": cal_counts["fused"] == 1,
               "max_abs_diff_fused_vs_unfused": parity,
               "max_abs_diff_vs_jnp": parity_jnp,
               "cpu_us_unfused": round(cal_counts["us_unfused"], 1)})


def check_invariants(doc: dict, baseline: dict | None = None) -> None:
    """Assert the report's perf/parity invariants (shared by the CI
    bench-smoke job and ``benchmarks/run.py``, which re-asserts them in the
    same run as the serving bench so the suite stays one command).

    When ``baseline`` (a previously checked-in BENCH_kernels.json doc) is
    given, wall-clock invariants are also checked *relative* to it: the
    pallas/jnp time ratio at the model shapes must not regress by more than
    25% vs the baseline's ratio.  Ratios (not absolute us) so a slower or
    faster CI machine doesn't flap the gate.
    """
    rows = {r["name"]: r for r in doc["rows"]}
    # jnp and pallas backends must agree bit for bit on integer codes
    parity = [r for n, r in rows.items() if n.startswith("tdvmm_parity")]
    assert parity and all(r["max_abs_diff"] == 0.0 for r in parity), parity
    # int8 code storage must reduce HBM bytes on the codes matmul
    ratios = [r for n, r in rows.items()
              if n.startswith("tdvmm_codes_bytes_ratio")]
    assert ratios and all(r["int8_reduces_hbm_bytes"] for r in ratios)
    # int4 packing must halve the code bytes bit-for-bit vs int8, and the
    # traced pallas launch must actually stream the packed operands
    int4 = [r for n, r in rows.items()
            if n.startswith("tdvmm_int4_codes_ratio")]
    assert int4, "no int4 packing rows"
    for r in int4:
        assert r["max_abs_diff_vs_int8"] == 0.0, r
        assert r["code_bytes_ratio"] <= 0.5, r
        assert r["packed_stream_verified"], r
        assert r["int4_halves_code_bytes"], r
    # the fused epilogue must materialize fewer (M, N) arrays
    fused = next(r for n, r in rows.items()
                 if n.startswith("tdvmm_fused_epilogue_opcount"))
    assert fused["fused_beats_unfused_opcount"], fused
    # the data-calibrated readout must be single-pass (ONE (M, N) HBM write)
    # and bit-for-bit with the legacy two-pass path
    cal = next(r for n, r in rows.items()
               if n.startswith("tdvmm_calibrated_epilogue"))
    assert cal["single_mn_write"], cal
    assert cal["max_abs_diff_fused_vs_unfused"] == 0.0, cal
    assert cal["max_abs_diff_vs_jnp"] == 0.0, cal
    # grouped projections (attn.qkv G=3, ssm.in_proj G=5) must run as ONE
    # launch with ONE input encode, bit-for-bit vs sequential — and the
    # ragged concat must not pad members beyond lane rounding
    grouped = [r for n, r in rows.items()
               if n.startswith("tdvmm_grouped_launch_count")]
    assert len(grouped) == 2, grouped
    for r in grouped:
        assert r["one_launch"] and r["grouped_launches"] == 1, r
        assert r["sequential_launches"] == r["group"], r
        assert r["encode_bytes_reduction"] == r["group"], r
        assert r["max_abs_diff_vs_sequential"] == 0.0, r
        assert r["max_abs_diff_jnp_vs_pallas"] == 0.0, r
        assert r["padded_n_overhead"] <= 1.05, r
    # autotuned pallas wall-clock: the model-shape rows must be table hits
    # with their chosen blocks recorded, and the headline shape must clear
    # the 3x-over-pre-autotune floor (9.1 GFLOP/s before the table existed)
    for shape in ("512x1024x4096", "256x896x896"):
        r = rows[f"tdvmm_pallas_{shape}"]
        assert r["autotune_hit"], r
        assert len(r["plan_blocks"]) == 3, r
    assert rows["tdvmm_pallas_512x1024x4096"]["gflops_per_s"] >= 27.3, \
        rows["tdvmm_pallas_512x1024x4096"]
    if baseline is not None:
        base_rows = {r["name"]: r for r in baseline.get("rows", [])}
        for shape in ("512x1024x4096", "256x896x896"):
            pk, jk = f"tdvmm_pallas_{shape}", f"tdvmm_jnp_{shape}"
            if pk not in base_rows or jk not in base_rows:
                continue
            base_ratio = (base_rows[pk]["us_per_call"]
                          / base_rows[jk]["us_per_call"])
            ratio = rows[pk]["us_per_call"] / rows[jk]["us_per_call"]
            assert ratio <= base_ratio * 1.25, (
                f"pallas/jnp ratio regressed at {shape}: "
                f"{ratio:.2f} vs baseline {base_ratio:.2f}")


def run():
    from repro.kernels.tdvmm import ops as tdops

    reset_rows()
    tdops.reset_autotune_report()
    k = jax.random.PRNGKey(0)

    bench_tdvmm_backends()
    bench_int8_vs_f32_codes()
    bench_int4_packing()
    bench_fused_epilogue()
    bench_grouped_projection()

    # tdvmm: jnp reference path (the kernel's oracle); AI accounting
    m, kk, n = 512, 2048, 512
    xq = jnp.round(jax.random.uniform(k, (m, kk), minval=-63, maxval=63))
    wq = jnp.round(jax.random.uniform(k, (kk, n), minval=-63, maxval=63))
    xs, ws = jnp.ones((m,)), jnp.ones((n,))
    fn = jax.jit(lambda a, b: tdvmm_matmul_ref(a, b, xs, ws, 1.0))
    us = time_call(fn, xq, wq)
    flops = 2 * m * kk * n
    emit("tdvmm_ref_512x2048x512", us,
         f"GFLOP/s={flops/us*1e-3:.1f}|AI_flops_per_byte="
         f"{flops/((m*kk+kk*n+m*n)*4):.0f}")

    # crossing: exact sort-based solve; the kernel replaces 30 HBM sweeps
    b, kk2, n2 = 8, 256, 512
    t_on = jax.random.uniform(k, (b, kk2))
    cur = jax.random.uniform(k, (kk2, n2), minval=0.01)
    fn2 = jax.jit(lambda t, c: crossing_ref(t, c, 0.3 * kk2))
    us2 = time_call(fn2, t_on, cur)
    emit("crossing_ref_8x256x512", us2,
         f"vmem_reuse_factor=iters(24)x|tile_KB={kk2*128*4//1024}")

    # ssd: chunked vs naive recurrence (the chunking win the kernel blocks)
    bb, L, H, P, G, S = 2, 512, 8, 64, 1, 64
    x = jax.random.normal(k, (bb, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k, (bb, L, H))) * 0.1
    a_log = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
    bmat = jax.random.normal(k, (bb, L, G, S)) * 0.3
    cmat = jax.random.normal(k, (bb, L, G, S)) * 0.3
    f_naive = jax.jit(lambda *a: ssd_naive(*a)[0])
    f_chunk = jax.jit(lambda *a: ssd_chunked(*a, 128)[0])
    us_n = time_call(f_naive, x, dt, a_log, bmat, cmat, iters=3)
    us_c = time_call(f_chunk, x, dt, a_log, bmat, cmat, iters=3)
    emit("ssd_naive_L512", us_n, "token-recurrence")
    emit("ssd_chunked_L512", us_c, f"speedup_vs_naive={us_n/us_c:.1f}x")

    save_json("BENCH_kernels.json",
              meta={"suite": "kernels",
                    "autotune": tdops.autotune_report()})


if __name__ == "__main__":
    run()
