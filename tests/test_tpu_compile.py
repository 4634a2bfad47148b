"""Mosaic compiles of the TD-VMM kernel variants at qwen1.5-0.5b widths, of
the batched expert launch at Trinity-Mini's, and of the decode step's
in-place page read at the three decode cells' shapes.

Each test lowers one ``ops.tdvmm_matmul`` launch with ``interpret=False``
for a described (not attached) TPU v5e chip and compiles it with the chip's
compiler, so a kernel Mosaic would refuse fails here rather than on the
chip.  Nothing runs: these say nothing about results or speed.  M is the
engine's decode width (8 slots) and prefill chunk (128 tokens) as
``chip_smoke.py`` serves them, and the calibration batch (8 x 128 rows) for
the data-calibrated readout.  Blocks come from the Mosaic autotune table, as
on the chip.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.plan import site_linear_shapes
from repro.kernels.paged_decode.paged_decode import paged_decode_kernel
from repro.kernels.tdvmm import ops, tdvmm
from repro.models import attention

SHAPES = site_linear_shapes(get_config("qwen1.5-0.5b"))
DECODE_M, PREFILL_M, CALIB_M = 8, 128, 8 * 128


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e:2x2 host.  Also pins x64 off (a
    Python-int index map would otherwise lower to int64, which Mosaic
    refuses) and keeps these compiles out of any persistent cache (an entry
    written without a chip cannot be read back)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_x64", "jax_enable_compilation_cache")}
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    for k, v in saved.items():
        jax.config.update(k, v)


def _compile(chip, fn, *avals):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text   # the kernel, not an XLA fallback


def _site(name):
    (k, n), = set(SHAPES[name]["matrices"])
    return k, n


def _blocks(m, k, n, dtype):
    return tdvmm.autotune_lookup(m, k, n, dtype, "mosaic")[0]


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
@pytest.mark.parametrize("site", ["attn.out", "ffn.in", "ffn.out"])
def test_fused_int8_runtime_window(chip, site, m):
    k, n = _site(site)
    blocks = _blocks(m, k, n, "int8")
    _compile(chip, lambda x, w, xs, ws, win: ops.tdvmm_matmul(
        x, w, xs, ws, gain=1e-4, out_bits=6, backend="pallas",
        interpret=False, code_dtype="int8", block_sizes=blocks,
        out_window=win),
        ((m, k), jnp.int8), ((k, n), jnp.int8), ((m,), jnp.float32),
        ((n,), jnp.float32), ((), jnp.float32))


def _qkv():
    mats = SHAPES["attn.qkv"]["matrices"]
    widths = tuple(tdvmm.padded_size(n, tdvmm.LANE, tdvmm.LANE)
                   for _, n in mats)
    return mats[0][0], widths


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
def test_shared_x_ragged_group(chip, m):
    """attn.qkv as one ragged concat launch with per-member windows."""
    k, widths = _qkv()
    n = sum(widths)
    bm, bk, bn = _blocks(m, k, n, "int8")
    blocks = (bm, bk, math.gcd(bn, *widths))
    _compile(chip, lambda x, w, xs, ws, win: ops.tdvmm_matmul(
        x, w, xs, ws, gain=1e-4, out_bits=6, backend="pallas",
        interpret=False, code_dtype="int8", block_sizes=blocks,
        group_widths=widths, out_window=win),
        ((m, k), jnp.int8), ((k, n), jnp.int8), ((m,), jnp.float32),
        ((n,), jnp.float32), ((len(widths),), jnp.float32))


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
def test_int4_packed(chip, m):
    """Nibble-packed codes unpacked in the kernel (p <= 3 plans)."""
    k, n = _site("ffn.in")
    blocks = _blocks(m, k, n, "int4")
    _compile(chip, lambda x, w, xs, ws, win: ops.tdvmm_matmul(
        x, w, xs, ws, gain=1e-4, out_bits=3, backend="pallas",
        interpret=False, code_dtype="int4", block_sizes=blocks,
        out_window=win),
        ((m, k), jnp.int8), ((k, n), jnp.int8), ((m,), jnp.float32),
        ((n,), jnp.float32), ((), jnp.float32))


@pytest.mark.parametrize("site", ["attn.qkv", "ffn.out"])
def test_data_calibrated_readout(chip, site):
    """Per-tile max launch + fused launch over the calibration batch."""
    if site == "attn.qkv":
        k, widths = _qkv()
        n = sum(widths)
        bm, bk, bn = _blocks(CALIB_M, k, n, "int8")
        blocks, group = (bm, bk, math.gcd(bn, *widths)), widths
    else:
        k, n = _site(site)
        blocks, group = _blocks(CALIB_M, k, n, "int8"), None
    _compile(chip, lambda x, w, xs, ws: ops.tdvmm_matmul(
        x, w, xs, ws, gain=1e-4, out_bits=6, backend="pallas",
        interpret=False, code_dtype="int8", block_sizes=blocks,
        group_widths=group),
        ((CALIB_M, k), jnp.int8), ((k, n), jnp.int8),
        ((CALIB_M,), jnp.float32), ((n,), jnp.float32))


# Trinity-Mini's held experts as one batched launch (G = 16 experts), at the
# dispatch capacities of its cell: the 96-slot decode step and the 512-row
# prefill chunk (dropless: a step's rows), with per-expert windows.
EXPERTS = 16


@pytest.mark.parametrize("m", [96, 512])
@pytest.mark.parametrize("k, n", [(2048, 1024), (1024, 2048)])
def test_batched_expert_launch(chip, k, n, m):
    blocks = _blocks(m, k, n, "int8")
    g = EXPERTS
    _compile(chip, lambda x, w, xs, ws, win: ops.tdvmm_matmul(
        x, w, xs, ws, gain=1e-4, out_bits=6, backend="pallas",
        interpret=False, code_dtype="int8", block_sizes=blocks,
        out_window=win),
        ((g, m, k), jnp.int8), ((g, k, n), jnp.int8), ((g, m), jnp.float32),
        ((g, n), jnp.float32), ((g,), jnp.float32))


# The decode step's in-place page read at the decode cells' shapes: (slots,
# heads, KV heads, head_dim, layers of the stacked pool, pages in it, pages
# a slot's table lists, window).  Trinity-Mini reads its 2 full layers
# through 512-page block rows and its 6 window layers through 160-page
# rings.
PAGED_DECODE = {
    "qwen05b-decode": (48, 16, 16, 64, 24, 48 * 64, 64, None),
    "qwen14b-decode": (64, 40, 8, 128, 8, 8192, 128, None),
    "trinity-mixed.full": (96, 32, 4, 128, 2, 96 * 512, 512, None),
    "trinity-mixed.ring": (96, 32, 4, 128, 6, 96 * 160, 160, 2048),
}


@pytest.mark.parametrize("cell", PAGED_DECODE)
def test_paged_decode_kernel(chip, cell):
    b, h, n_kv, hd, layers, pages, per_slot, window = PAGED_DECODE[cell]
    page, width = 16, n_kv * hd

    def read(q_bd, k, v, layer, tables, pos, count):
        if window is None:
            first = jnp.zeros_like(pos)
        else:
            tables, first = attention._window_pages(tables, pos, window, page)
        return paged_decode_kernel(q_bd, k, v, layer, tables, first * page,
                                   count, pos, head_dim=hd, window=window)

    pool = ((layers, pages + 1, page, width), jnp.bfloat16)
    _compile(chip, read, ((b, h, width), jnp.bfloat16), pool, pool,
             ((), jnp.int32), ((b, per_slot), jnp.int32), ((b,), jnp.int32),
             ((b,), jnp.int32))
