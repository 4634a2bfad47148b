"""Per-kernel validation: shape/dtype sweeps + allclose vs pure-jnp oracles.

Kernels run in interpret mode (Python execution of the kernel body) on CPU;
on TPU the same pallas_call compiles to Mosaic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.crossing.crossing import crossing_kernel
from repro.kernels.crossing.ref import crossing_ref
from repro.kernels.ssd.ref import ssd_naive
from repro.kernels.ssd.ssd import ssd_kernel
from repro.kernels.tdvmm.ref import tdvmm_matmul_ref
from repro.kernels.tdvmm.tdvmm import (
    autotune_blocks, pad_to_blocks, tdvmm_fused_kernel, tdvmm_matmul_kernel)
from repro.models.ssm import ssd_chunked


# --------------------------------------------------------------------------
# tdvmm
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (128, 256, 128, 128, 128, 128),
    (256, 1024, 256, 128, 512, 128),
    (128, 128, 384, 64, 128, 128),
    (512, 512, 128, 256, 256, 64),
])
def test_tdvmm_shapes(m, k, n, bm, bk, bn):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + n))
    xq = jnp.round(jax.random.uniform(kx, (m, k), minval=-63, maxval=63))
    wq = jnp.round(jax.random.uniform(kw, (k, n), minval=-63, maxval=63))
    out = tdvmm_matmul_kernel(xq, wq, bm=bm, bk=bk, bn=bn, interpret=True)
    ref = tdvmm_matmul_ref(xq, wq, jnp.ones((m,)), jnp.ones((n,)), 1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("bits", [4, 6, 8])
def test_tdvmm_bit_widths(bits):
    lv = (1 << bits) - 1
    kx, kw = jax.random.split(jax.random.PRNGKey(bits))
    xq = jnp.round(jax.random.uniform(kx, (128, 256), minval=-lv, maxval=lv))
    wq = jnp.round(jax.random.uniform(kw, (256, 128), minval=-lv, maxval=lv))
    out = tdvmm_matmul_kernel(xq, wq, interpret=True)
    ref = jnp.dot(xq, wq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
    # integer-exactness: charge sums are exact in f32 up to 2^24
    assert float(jnp.max(jnp.abs(out - jnp.round(out)))) == 0.0


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (128, 256, 128, 128, 128, 128),
    (256, 1024, 256, 128, 512, 128),
    (64, 512, 128, 32, 256, 128),
])
def test_tdvmm_int8_kernel_exact(m, k, n, bm, bk, bn):
    """int8 codes -> int32 accumulation: exact vs int64 numpy."""
    rng = np.random.default_rng(m + n)
    xq = rng.integers(-127, 128, (m, k), dtype=np.int8)
    wq = rng.integers(-127, 128, (k, n), dtype=np.int8)
    out = tdvmm_matmul_kernel(jnp.asarray(xq), jnp.asarray(wq),
                              bm=bm, bk=bk, bn=bn, interpret=True)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(out), xq.astype(np.int64) @ wq.astype(np.int64))


def test_tdvmm_int8_kernel_exact_beyond_f32_envelope():
    """Saturated codes drive |acc| past 2^24 onto odd values no f32 holds —
    the int32 path must still be exact."""
    k = 2048
    xq = np.full((32, k), 127, np.int8)
    wq = np.full((k, 128), 127, np.int8)
    wq[0, 0] = 126
    exact = xq.astype(np.int64) @ wq.astype(np.int64)
    assert np.max(exact) > (1 << 24) and int(exact[0, 0]) % 2 == 1
    out = tdvmm_matmul_kernel(jnp.asarray(xq), jnp.asarray(wq), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), exact)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32])
def test_tdvmm_batched_expert_grid(dtype):
    """(E, M, K) x (E, K, N) batched grid vs per-expert einsum."""
    e, m, k, n = 3, 64, 256, 128
    rng = np.random.default_rng(e)
    xq = rng.integers(-63, 64, (e, m, k)).astype(dtype)
    wq = rng.integers(-63, 64, (e, k, n)).astype(dtype)
    out = tdvmm_matmul_kernel(jnp.asarray(xq), jnp.asarray(wq), interpret=True)
    exact = np.einsum("emk,ekn->emn", xq.astype(np.int64), wq.astype(np.int64))
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64), exact)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32])
def test_tdvmm_fused_kernel_matches_oracle(dtype):
    """Fused gain+readout+rescale epilogue vs the pure-jnp oracle."""
    e, m, k, n = 2, 64, 256, 128
    rng = np.random.default_rng(7)
    xq = rng.integers(-63, 64, (e, m, k)).astype(dtype)
    wq = rng.integers(-63, 64, (e, k, n)).astype(dtype)
    xs = rng.uniform(0.5, 2.0, (e, m)).astype(np.float32)
    ws = rng.uniform(0.5, 2.0, (e, n)).astype(np.float32)
    gain, out_bits, out_scale = 1e-4, 6, 0.5
    got = tdvmm_fused_kernel(
        jnp.asarray(xq), jnp.asarray(wq),
        jnp.asarray(xs)[..., :, None], jnp.asarray(ws)[..., None, :],
        gain=gain, out_bits=out_bits, out_scale=out_scale, interpret=True)
    assert got.dtype == jnp.float32
    ref = tdvmm_matmul_ref(jnp.asarray(xq), jnp.asarray(wq), jnp.asarray(xs),
                           jnp.asarray(ws), gain=gain, out_bits=out_bits,
                           out_scale=out_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32])
def test_tdvmm_shared_x_grouped_grid(dtype):
    """(1, M, K) x (G, K, N) shared-input grouped grid: one code copy feeds
    every group tile, exactly equal to the per-tile einsum."""
    g, m, k, n = 4, 64, 256, 128
    rng = np.random.default_rng(11)
    xq = rng.integers(-63, 64, (1, m, k)).astype(dtype)
    wq = rng.integers(-63, 64, (g, k, n)).astype(dtype)
    out = tdvmm_matmul_kernel(jnp.asarray(xq), jnp.asarray(wq), interpret=True)
    exact = np.einsum("mk,gkn->gmn", xq[0].astype(np.int64),
                      wq.astype(np.int64))
    assert out.shape == (g, m, n)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64), exact)


def test_tdvmm_shared_x_ops_matches_sequential():
    """ops.tdvmm_matmul with 2-D x against a (G, K, N) bank == the G
    sequential 2-D launches, bit for bit, on both backends and with scalar,
    per-member, and data-calibrated readout windows."""
    from repro.kernels.tdvmm import ops
    g, m, k, n = 3, 33, 96, 40
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    xq = jnp.round(jax.random.uniform(kx, (m, k), minval=-63, maxval=63))
    wq = jnp.round(jax.random.uniform(kw, (g, k, n), minval=-63, maxval=63))
    xs = jax.random.uniform(jax.random.PRNGKey(1), (m,), minval=0.5, maxval=2.0)
    ws = jax.random.uniform(jax.random.PRNGKey(2), (g, n), minval=0.5, maxval=2.0)
    for out_bits, out_scale in [(None, None), (6, 0.5),
                                (6, (0.5, 0.25, 1.0)), (6, None)]:
        for backend in ("jnp", "pallas"):
            got = ops.tdvmm_matmul(xq, wq, xs, ws, gain=1e-4,
                                   out_bits=out_bits, out_scale=out_scale,
                                   backend=backend)
            assert got.shape == (g, m, n)
            for i in range(g):
                s = out_scale[i] if isinstance(out_scale, tuple) else out_scale
                seq = ops.tdvmm_matmul(xq, wq[i], xs, ws[i], gain=1e-4,
                                       out_bits=out_bits, out_scale=s,
                                       backend=backend)
                np.testing.assert_array_equal(np.asarray(got[i]),
                                              np.asarray(seq))


def test_tdvmm_shared_x_vjp_sums_over_group():
    """The shared input's cotangent accumulates over all G tiles (matching
    G independent matmuls that share x)."""
    from repro.kernels.tdvmm import ops
    g, m, k, n = 3, 16, 48, 24
    xq = jnp.round(jax.random.uniform(jax.random.PRNGKey(3), (m, k),
                                      minval=-31, maxval=31))
    wq = jnp.round(jax.random.uniform(jax.random.PRNGKey(4), (g, k, n),
                                      minval=-31, maxval=31))
    xs = jnp.ones((m,))
    ws = jnp.ones((g, n))

    def grouped(x_, w_):
        return jnp.sum(ops.tdvmm_matmul(x_, w_, xs, ws, gain=1e-3,
                                        backend="jnp") ** 2)

    def sequential(x_, w_):
        return sum(jnp.sum(ops.tdvmm_matmul(x_, w_[i], xs, ws[i], gain=1e-3,
                                            backend="jnp") ** 2)
                   for i in range(g))

    gx, gw = jax.grad(grouped, argnums=(0, 1))(xq, wq)
    gx2, gw2 = jax.grad(sequential, argnums=(0, 1))(xq, wq)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw2),
                               rtol=1e-6, atol=1e-6)


def test_tdvmm_batched_x_w_mismatch_raises():
    from repro.kernels.tdvmm import ops
    with pytest.raises(ValueError, match="shared-x"):
        ops.tdvmm_matmul(jnp.ones((2, 8, 16)), jnp.ones((3, 16, 8)),
                         jnp.ones((2, 8)), jnp.ones((3, 8)), backend="jnp")


def test_autotune_table_and_padding_alignment():
    """Autotuned blocks are always launchable after pad_to_blocks, and int8
    padding respects the (32, 128) minimum tile."""
    for (m, k, n) in [(512, 1024, 4096), (100, 300, 50), (8, 128, 64),
                      (1, 1, 1)]:
        for dtype in (jnp.int8, jnp.float32):
            bm, bk, bn = autotune_blocks(m, k, n, dtype)
            x = jnp.zeros((m, k), dtype)
            w = jnp.zeros((k, n), dtype)
            xp, wp = pad_to_blocks(x, w, bm, bk, bn)
            mp, kp = xp.shape
            np_ = wp.shape[1]
            sub = 32 if dtype == jnp.int8 else 8
            assert mp % sub == 0 and kp % 128 == 0 and np_ % 128 == 0
            for dim, blk in [(mp, bm), (kp, bk), (np_, bn)]:
                assert dim % min(blk, dim) == 0
    # int8 heuristic doubles the K block at equal VMEM bytes
    assert autotune_blocks(999, 4096, 999, jnp.int8)[1] == \
        2 * autotune_blocks(999, 4096, 999, jnp.float32)[1]


@pytest.mark.parametrize("k", [96, 95, 1])
def test_tdvmm_int4_ops_matches_int8(k):
    """Nibble-packed launches (p <= 3 codes, two per byte, unpacked in-VMEM)
    are bit-for-bit identical to int8 — including odd K, where the pack pads
    a zero nibble that integrates zero charge."""
    from repro.kernels.tdvmm import ops
    m, n = 17, 40
    kx, kw = jax.random.split(jax.random.PRNGKey(k))
    xq = jnp.round(jax.random.uniform(kx, (m, k), minval=-7, maxval=7)
                   ).astype(jnp.int8)
    wq = jnp.round(jax.random.uniform(kw, (k, n), minval=-7, maxval=7)
                   ).astype(jnp.int8)
    xs = jax.random.uniform(jax.random.PRNGKey(1), (m,), minval=0.5,
                            maxval=2.0)
    ws = jax.random.uniform(jax.random.PRNGKey(2), (n,), minval=0.5,
                            maxval=2.0)
    for out_bits, out_scale in [(None, None), (6, 0.5), (6, None)]:
        ref = ops.tdvmm_matmul(xq, wq, xs, ws, gain=1e-3, out_bits=out_bits,
                               out_scale=out_scale, backend="jnp")
        for code_dtype in ("int8", "int4"):
            got = ops.tdvmm_matmul(xq, wq, xs, ws, gain=1e-3,
                                   out_bits=out_bits, out_scale=out_scale,
                                   backend="pallas", code_dtype=code_dtype)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=f"{code_dtype} {out_scale}")


def test_tdvmm_ragged_group_widths_matches_sequential():
    """A ragged concat launch (group_widths) equals the per-member 2-D
    launches bit for bit on both backends, for scalar, per-member-tuple,
    and data-calibrated readout windows."""
    from repro.kernels.tdvmm import ops
    m, k, widths = 9, 64, (128, 64)
    n = sum(widths)
    kx, kw = jax.random.split(jax.random.PRNGKey(5))
    xq = jnp.round(jax.random.uniform(kx, (m, k), minval=-63, maxval=63))
    wq = jnp.round(jax.random.uniform(kw, (k, n), minval=-63, maxval=63))
    xs = jax.random.uniform(jax.random.PRNGKey(6), (m,), minval=0.5,
                            maxval=2.0)
    ws = jax.random.uniform(jax.random.PRNGKey(7), (n,), minval=0.5,
                            maxval=2.0)
    # bn=64 divides every member width, so calibrated slots land on member
    # boundaries — the same invariant layers.td_grouped_matmul keeps via gcd.
    blocks = (64, 64, 64)
    for out_bits, out_scale in [(None, None), (6, 0.5), (6, (0.5, 0.25)),
                                (6, None)]:
        for backend in ("jnp", "pallas"):
            got = ops.tdvmm_matmul(xq, wq, xs, ws, gain=1e-4,
                                   out_bits=out_bits, out_scale=out_scale,
                                   backend=backend, block_sizes=blocks,
                                   group_widths=widths)
            off = 0
            for i, wd in enumerate(widths):
                s = out_scale[i] if isinstance(out_scale, tuple) else out_scale
                seq = ops.tdvmm_matmul(
                    xq, wq[:, off:off + wd], xs, ws[off:off + wd],
                    gain=1e-4, out_bits=out_bits, out_scale=s,
                    backend=backend, block_sizes=blocks)
                np.testing.assert_array_equal(
                    np.asarray(got[:, off:off + wd]), np.asarray(seq),
                    err_msg=f"{backend} member {i} window {out_scale}")
                off += wd


def test_tdvmm_fused_calibration_matches_unfused():
    """The fused data-calibrated readout (per-tile max|z| launch, then the
    fused kernel with the per-expert windows: one (M, N) HBM write) is
    bit-for-bit with the unfused formulation — ``ops._epilogue`` on the
    kernel's raw accumulation — and with the jnp oracle, batched experts
    included."""
    from repro.kernels.tdvmm import ops
    e, m, k, n = 2, 33, 96, 40
    kx, kw = jax.random.split(jax.random.PRNGKey(8))
    xq = jnp.round(jax.random.uniform(kx, (e, m, k), minval=-63, maxval=63))
    wq = jnp.round(jax.random.uniform(kw, (e, k, n), minval=-63, maxval=63))
    xs = jax.random.uniform(jax.random.PRNGKey(9), (e, m), minval=0.5,
                            maxval=2.0)
    ws = jax.random.uniform(jax.random.PRNGKey(10), (e, n), minval=0.5,
                            maxval=2.0)
    kwargs = dict(gain=1e-4, out_bits=6, out_scale=None)
    fused = ops.tdvmm_matmul(xq, wq, xs, ws, backend="pallas", **kwargs)
    bm, bk, bn = autotune_blocks(m, k, n, xq.dtype)
    xp, wp = pad_to_blocks(xq, wq, bm, bk, bn)
    acc = tdvmm_matmul_kernel(xp, wp, bm=bm, bk=bk, bn=bn,
                              interpret=True)[:, :m, :n]
    unfused = ops._epilogue(acc, xs, ws, **kwargs)
    oracle = ops.tdvmm_matmul(xq, wq, xs, ws, backend="jnp", **kwargs)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(oracle))


# --------------------------------------------------------------------------
# tdvmm: launches, encodes, output writes and code bytes, counted in the
# traced program (no timing)
# --------------------------------------------------------------------------
def _eqns(jx):
    """Every equation of a jaxpr, recursing into nested (pjit / custom_vjp /
    scan / pallas_call) sub-jaxprs."""
    for eqn in jx.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)
                elif hasattr(sub, "jaxpr"):
                    yield from _eqns(sub.jaxpr)


def _traced(fn, *args):
    return list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))


def _count(eqns, prim):
    return sum(e.primitive.name == prim for e in eqns)


def _mn_writes(fn, args, m, n):
    """Top-level equations that materialize an (..., M, N) array: each is an
    HBM round trip of the whole output.  A pallas_call's own output is one
    write; its body's blocks live in VMEM, so it is not entered.  View
    primitives write nothing of their own."""
    views = {"squeeze", "reshape", "broadcast_in_dim", "transpose"}

    def walk(jx):
        count = 0
        for eqn in jx.eqns:
            if eqn.primitive.name in views:
                continue
            mn_out = any(getattr(v.aval, "shape", ())[-2:] == (m, n)
                         for v in eqn.outvars)
            subs = [sub for val in eqn.params.values()
                    for sub in (val if isinstance(val, (list, tuple))
                                else [val])
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr")]
            if eqn.primitive.name == "pallas_call" or not subs:
                count += mn_out
            else:
                count += sum(walk(sub if hasattr(sub, "eqns") else sub.jaxpr)
                             for sub in subs)
        return count

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("m,k,ns", [
    (64, 896, (896, 128, 128)),                   # attn.qkv: wq / wk / wv
    (64, 512, (1024, 1024, 128, 128, 16)),        # ssm.in_proj: z/x/B/C/dt
], ids=["attn_qkv", "ssm_in_proj"])
def test_grouped_projection_is_one_launch(m, k, ns):
    """G same-input projections run as ONE ragged concat launch with ONE
    input encode (G sequential td_matmuls: G of each), each member padded
    only to the lane, and bit-for-bit equal to the sequential calls on both
    backends."""
    from repro.core.layers import (TDVMMLayerConfig, td_grouped_matmul,
                                   td_matmul)
    g = len(ns)
    x = jax.random.normal(jax.random.PRNGKey(g), (m, k))
    ws = tuple(jax.random.normal(jax.random.PRNGKey(17 + i), (k, n)) * 0.1
               for i, n in enumerate(ns))
    outs = {}
    for backend in ("jnp", "pallas"):
        cfg = TDVMMLayerConfig(enabled=True, backend=backend)
        grouped = jax.jit(lambda x_, ws_, c=cfg: td_grouped_matmul(x_, ws_, c))
        seq = jax.jit(lambda x_, ws_, c=cfg: tuple(td_matmul(x_, w, c)
                                                   for w in ws_))
        eg, es = _traced(grouped, x, ws), _traced(seq, x, ws)
        prim = "dot_general" if backend == "jnp" else "pallas_call"
        assert _count(es, prim) == g * _count(eg, prim), backend
        if backend == "jnp":
            assert _count(eg, "dot_general") == 1
            encodes = [sum(e.primitive.name == "convert_element_type"
                           and any(v.aval.shape == (m, k)
                                   and v.aval.dtype == jnp.int8
                                   for v in e.outvars) for e in eqns)
                       for eqns in (eg, es)]
            assert encodes == [1, g]
            n_total = next(e.invars[1].aval.shape[-1] for e in eg
                           if e.primitive.name == "dot_general")
            assert n_total <= 1.05 * sum(ns), (n_total, ns)
        outs[backend] = grouped(x, ws)
        for a, b in zip(outs[backend], seq(x, ws)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(outs["jnp"], outs["pallas"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("out_scale,launches", [(0.5, 1), (None, 2)],
                         ids=["fixed_window", "data_calibrated"])
def test_fused_readout_writes_one_mn_output(out_scale, launches):
    """The Pallas readout, with a fixed window or calibrated from the data
    (a per-tile max launch first), writes the (M, N) output to HBM once,
    already in model units; the jnp backend's unfused epilogue writes more."""
    from repro.kernels.tdvmm import ops
    m, k, n = 256, 1024, 512
    xc = jnp.zeros((m, k), jnp.int8)
    wc = jnp.zeros((k, n), jnp.int8)
    xs, ws = jnp.ones((m,)), jnp.ones((n,))
    writes = {}
    for backend in ("jnp", "pallas"):
        fn = jax.jit(lambda *a, b=backend: ops.tdvmm_matmul(
            *a, gain=1e-4, out_bits=6, out_scale=out_scale, backend=b))
        writes[backend] = _mn_writes(fn, (xc, wc, xs, ws), m, n)
        if backend == "pallas":
            assert _count(_traced(fn, xc, wc, xs, ws),
                          "pallas_call") == launches
    assert writes["pallas"] == 1
    assert writes["jnp"] > writes["pallas"]


@pytest.mark.parametrize("code_dtype", ["int8", "f32"])
def test_code_dtype_reaches_the_contraction(code_dtype):
    """int8 code storage is what the codes matmul consumes (a quarter of the
    f32 bytes), not a cast back to f32 before the dot."""
    from repro.kernels.tdvmm import ops
    m, k, n = 512, 2048, 512
    dt = jnp.int8 if code_dtype == "int8" else jnp.float32
    args = (jnp.zeros((m, k), dt), jnp.zeros((k, n), dt), jnp.ones((m,)),
            jnp.ones((n,)))
    fn = jax.jit(lambda *a: ops.tdvmm_matmul(
        *a, gain=1e-4, out_bits=6, out_scale=0.5, backend="jnp"))
    dot = next(e for e in _traced(fn, *args)
               if e.primitive.name == "dot_general")
    assert dot.invars[0].aval.dtype == dt


def test_int4_launch_streams_half_the_code_bytes():
    """The int4 Pallas launch streams nibble-packed codes: its operands come
    to at most 0.6x the int8 launch's bytes (the scales ride in both)."""
    from repro.kernels.tdvmm import ops
    m, k, n = 512, 1024, 4096
    args = (jnp.zeros((m, k), jnp.int8), jnp.zeros((k, n), jnp.int8),
            jnp.ones((m,)), jnp.ones((n,)))
    streamed = {}
    for code_dtype in ("int8", "int4"):
        fn = jax.jit(lambda *a, c=code_dtype: ops.tdvmm_matmul(
            *a, gain=1e-4, out_bits=6, out_scale=0.5, backend="pallas",
            code_dtype=c))
        call = next(e for e in _traced(fn, *args)
                    if e.primitive.name == "pallas_call")
        streamed[code_dtype] = sum(
            int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
            for v in call.invars)
    assert streamed["int4"] <= 0.6 * streamed["int8"], streamed


def test_autotune_table_answers_the_reference_shapes():
    """Model-width launches are table hits on this platform, with their
    blocks recorded in the process's autotune report."""
    from repro.kernels.tdvmm import ops
    for m, k, n in [(512, 1024, 4096), (256, 896, 896)]:
        plan = ops.plan_kernel("pallas", m, k, n, "f32")
        assert plan.autotune_hit, (m, k, n, plan)
        entry = ops.autotune_report()["entries"][f"{m}x{k}x{n}:float32"]
        assert entry["hit"] and len(entry["blocks"]) == 3


# --------------------------------------------------------------------------
# crossing
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,k,n", [(1, 32, 128), (4, 64, 128), (2, 128, 256)])
def test_crossing_shapes(b, k, n):
    kt, kc = jax.random.split(jax.random.PRNGKey(b * k + n))
    t_on = jax.random.uniform(kt, (b, k), maxval=1.0)
    cur = jax.random.uniform(kc, (k, n), minval=0.01, maxval=1.0)
    charge = float(0.3 * k)
    got = crossing_kernel(t_on, cur, charge, t_hi=2.0, iters=30, interpret=True)
    ref = crossing_ref(t_on, cur, charge)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 8), st.floats(0.05, 0.9))
def test_crossing_bisection_converges(seed, frac):
    """Property: bisection resolves the exact (sort-based) crossing to the
    bisection tolerance for random currents/charges."""
    k, n = 32, 128
    kt, kc = jax.random.split(jax.random.PRNGKey(seed))
    t_on = jax.random.uniform(kt, (2, k), maxval=1.0)
    cur = jax.random.uniform(kc, (k, n), minval=0.05, maxval=1.0)
    charge = float(frac * 0.5 * k)
    got = crossing_kernel(t_on, cur, charge, t_hi=2.0, iters=32, interpret=True)
    ref = crossing_ref(t_on, cur, charge)
    assert float(jnp.max(jnp.abs(got - ref))) < 2.0 / (1 << 30) + 1e-6


# --------------------------------------------------------------------------
# ssd
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,l,h,p,g,s,chunk", [
    (2, 64, 4, 16, 2, 8, 16),
    (1, 128, 2, 32, 1, 16, 32),
    (2, 32, 8, 8, 8, 8, 8),     # G == H (no grouping)
    (1, 64, 4, 64, 1, 64, 64),  # full-width tiles
])
def test_ssd_shapes(b, l, h, p, g, s, chunk):
    keys = jax.random.split(jax.random.PRNGKey(l + h), 5)
    x = jax.random.normal(keys[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, l, h))) * 0.1
    a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
    bb = jax.random.normal(keys[2], (b, l, g, s)) * 0.3
    cc = jax.random.normal(keys[3], (b, l, g, s)) * 0.3
    yk = ssd_kernel(x, dt, a_log, bb, cc, chunk=chunk, interpret=True)
    yn, _ = ssd_naive(x, dt, a_log, bb, cc)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yn),
                               rtol=1e-4, atol=1e-4)


def test_ssd_kernel_matches_chunked_jnp():
    """Kernel vs the pjit-path chunked implementation (must be identical
    algebra, so tolerance is tight)."""
    b, l, h, p, g, s = 2, 128, 4, 16, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, l, h))) * 0.1
    a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
    bb = jax.random.normal(keys[2], (b, l, g, s)) * 0.3
    cc = jax.random.normal(keys[3], (b, l, g, s)) * 0.3
    yk = ssd_kernel(x, dt, a_log, bb, cc, chunk=32, interpret=True)
    yc, _ = ssd_chunked(x, dt, a_log, bb, cc, 32)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yc),
                               rtol=1e-5, atol=1e-5)


def test_ssd_state_carry_across_chunks():
    """Chunk boundaries must be invisible: chunk=L vs chunk=L/4 agree."""
    b, l, h, p, g, s = 1, 64, 2, 16, 1, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(keys[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, l, h))) * 0.1
    a_log = jnp.zeros((h,))
    bb = jax.random.normal(keys[2], (b, l, g, s)) * 0.3
    cc = jax.random.normal(keys[3], (b, l, g, s)) * 0.3
    y1 = ssd_kernel(x, dt, a_log, bb, cc, chunk=64, interpret=True)
    y2 = ssd_kernel(x, dt, a_log, bb, cc, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
