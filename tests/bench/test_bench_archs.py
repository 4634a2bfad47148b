"""Architectures found from the configuration file (``bench/archs``).

The Qwen2 package gives the numbers the benchmark gave before it became
a package (pinned below from that code, on both configuration files), a
stub architecture with batched expert launches goes through the harness's
layout check and the launch and work sums with no change to them, and the
lookup refuses a class that no package, or two, claim.
"""
from __future__ import annotations

import hashlib
import json
import math

import jax
import numpy as np
import pytest

from benchcells import ROOT, check_size, no_compile_cache  # noqa: F401
from bench import archs, harness, work  # noqa: E402

V5E = work.peaks("TPU v5 lite")
STUB_ROOT = ROOT / "tests" / "bench" / "archs_stub"
TWIN_ROOT = ROOT / "tests" / "bench" / "archs_twin"


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def qwen_shapes(layers, d, q, kv, f, vp, tied):
    blk = "blocks/seg0/"
    out = {"embed/table": (vp, d), "ln_f/scale": (d,),
           blk + "ln1/scale": (layers, d), blk + "ln2/scale": (layers, d),
           blk + "attn/wq/w": (layers, d, q), blk + "attn/wq/b": (layers, q),
           blk + "attn/wk/w": (layers, d, kv), blk + "attn/wk/b": (layers, kv),
           blk + "attn/wv/w": (layers, d, kv), blk + "attn/wv/b": (layers, kv),
           blk + "attn/wo/w": (layers, q, d),
           blk + "ffn/w_gate/w": (layers, d, f), blk + "ffn/w_up/w": (layers, d, f),
           blk + "ffn/w_down/w": (layers, f, d)}
    if not tied:
        out["head/w"] = (d, vp)
    return out


# Read from the code before the move: (decode slots, prefill chunk), the
# launches of a decode step and of a prefill chunk, the least seconds of
# each decode launch on a v5e, least_kernel_seconds and kernel_launches
# for (60 decode steps of 48 slots, 5 chunks of 256) and (10 of 8, 39 of
# 512), and model_seconds(4000 tokens, 2880 head rows, the position sum
# 618383 of RANGES).
RANGES = [(0, 1000), (1000, 1111), (17, 40)]
PINNED = {
    "qwen1.5-0.5b": {
        "shapes": qwen_shapes(24, 1024, 1024, 1024, 2816, 152064, True),
        "cell": (48, 256),
        "decode": [("attn.qkv", 48, 1024, 3072, 24), ("attn.out", 48, 1024, 1024, 24),
                   ("ffn.in", 48, 1024, 2816, 48), ("ffn.out", 48, 2816, 1024, 24)],
        "prefill": [("attn.qkv", 256, 1024, 3072, 24), ("attn.out", 256, 1024, 1024, 24),
                    ("ffn.in", 256, 1024, 2816, 48), ("ffn.out", 256, 2816, 1024, 24)],
        "least_48": [4.6211282051282055e-06, 1.5803858363858364e-06,
                     4.241035409035409e-06, 3.925958485958486e-06],
        "least": (0.03055225904761905, 0.047771542974358976),
        "launches": (7800, 5880),
        "model_s": 0.01113303357703073,
    },
    "qwen2.5-14b-1chip": {
        "shapes": qwen_shapes(8, 5120, 5120, 1024, 13824, 152064, False),
        "cell": (8, 512),
        "decode": [("attn.qkv", 8, 5120, 7168, 8), ("attn.out", 8, 5120, 5120, 8),
                   ("ffn.in", 8, 5120, 13824, 16), ("ffn.out", 8, 13824, 5120, 8),
                   ("head", 8, 5120, 152064, 1)],
        "prefill": [("attn.qkv", 512, 5120, 7168, 8), ("attn.out", 512, 5120, 5120, 8),
                    ("ffn.in", 512, 5120, 13824, 16), ("ffn.out", 512, 13824, 5120, 8),
                    ("head", 1, 5120, 152064, 1)],
        "least_48": [4.6791423687423687e-05, 3.350818070818071e-05, 8.996196336996337e-05,
                     8.843158974358975e-05, 0.0009865808644688645],
        "least": (0.24743175189255187, 0.297488817867375),
        "launches": (2665, 2009),
        "model_s": 0.056749984947786256,
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_qwen2_package_gives_the_numbers_of_before_the_move(name):
    c, want = cfg(name), PINNED[name]
    arch = archs.find(c)
    assert arch.name == "qwen2"
    assert arch.reference.param_shapes(c) == want["shapes"]
    slots, chunk = want["cell"]
    for rows, head, key in ((slots, slots, "decode"), (chunk, 1, "prefill")):
        got = arch.work.step_launches(c, rows, head)
        assert [g for _, g, *_ in got] == [1] * len(got)
        assert [(s, m, k, n, cnt) for s, _, m, k, n, cnt in got] == want[key]
    assert [work.launch_cost(g, m, k, n, V5E)[0] for _, g, m, k, n, _ in
            arch.work.step_launches(c, 48, 48)] == want["least_48"]
    assert (work.least_kernel_seconds(c, V5E, 60, 48, 5, 256),
            work.least_kernel_seconds(c, V5E, 10, 8, 39, 512)) == want["least"]
    assert (work.kernel_launches(c, 60, 48, 5, 256),
            work.kernel_launches(c, 10, 8, 39, 512)) == want["launches"]
    assert work.model_seconds(c, V5E, 4000, 2880, RANGES) == want["model_s"]


# sha256 of the reference's logits (seed 2**35 + 3, calibrated on a fixed
# batch, two sequences) at the check's test size, read before the move.
LOGITS_SHA256 = {
    True: "49ab8816349461e8146ce4f41d32827f7ea9b19be8dbf10a406a51732e66a4a9",
    False: "81872c9f54b536efdca68549c8e07624067eaec27f492dc210bfaeb726737111",
}


@pytest.mark.parametrize("tied", [True, False], ids=["tied-head", "analog-head"])
def test_reference_logits_bit_identical_to_before_the_move(tied, no_compile_cache):
    _, c, _ = check_size(tied)
    with jax.default_matmul_precision("highest"):
        ref = archs.find(c).reference.Reference(c, 2**35 + 3)
        ref.calibrate(np.random.default_rng(1).integers(0, 8192, (2, 32), dtype=np.int32))
        seqs = [np.random.default_rng(2).integers(0, 8192, 40).astype(np.int32),
                np.random.default_rng(3).integers(0, 8192, 23).astype(np.int32)]
        got = ref.served_logits(seqs, [30, 10], 56, 8, lambda j, lg: np.asarray(lg))
    digest = hashlib.sha256(b"".join(np.ascontiguousarray(g).tobytes() for g in got))
    assert digest.hexdigest() == LOGITS_SHA256[tied]


@pytest.fixture
def stub(monkeypatch):
    monkeypatch.setattr(archs, "__path__", archs.__path__ + [str(STUB_ROOT)])
    return json.loads((STUB_ROOT / "stub-moe.json").read_text())


def test_stub_architecture_goes_through_the_harness_unchanged(stub):
    from repro.models import model
    arch = archs.find(stub)
    assert arch.name == "stubmoe"
    mcfg = arch.program.model_config(stub)
    assert (mcfg.moe.n_experts, mcfg.swa_window) == (4, 8)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), mcfg))
    harness.check_layout(shapes, stub)
    with pytest.raises(SystemExit, match="parameter tree differs"):
        harness.check_layout(shapes, dict(stub, num_experts=5))
    # One decode step of 8 rows, two prefill chunks of 16 rows: per step
    # qkv and out x3 layers, ffn 2 + 1 on the dense layer, on the two
    # sparse layers expert in 4, out 2 (G = 4 experts of ceil(M * 2 / 4)
    # rows) and shared in 4, out 2, and the head.
    launches = work.window_launches(stub, 1, 8, 2, 16)
    assert [(s, g, m, c) for s, g, m, _, _, c in launches
            if s.startswith("moe.expert")] == [
        ("moe.expert.in", 4, 4, 4), ("moe.expert.out", 4, 4, 2),
        ("moe.expert.in", 4, 8, 8), ("moe.expert.out", 4, 8, 4)]
    per_step = 3 + 3 + 2 + 1 + 4 + 2 + 4 + 2 + 1
    assert work.kernel_launches(stub, 1, 8, 2, 16) == 3 * per_step
    assert work.launch_cost(4, 4, 64, 32, V5E)[0] == pytest.approx(
        4 * work.launch_cost(1, 4, 64, 32, V5E)[0])
    assert work.least_kernel_seconds(stub, V5E, 1, 8, 2, 16) == pytest.approx(
        sum(c * work.launch_cost(g, m, k, n, V5E)[0] for _, g, m, k, n, c in launches))
    # Positions 0..11 with an 8-wide window: 1 + 2 + ... + 8 + 4 * 8 keys.
    int8, bf16 = arch.work.model_work(stub, 12, 1, [(0, 5), (5, 12)])
    assert bf16 == 4 * 4 * 16 * 3 * (36 + 32)
    assert work.model_seconds(stub, V5E, 12, 1, [(0, 5), (5, 12)]) == pytest.approx(
        int8 / 393e12 + bf16 / 197e12)


@pytest.mark.parametrize("case", ["unknown", "claimed-twice", "missing"])
def test_a_class_claimed_by_no_package_or_two_is_an_error(case, monkeypatch):
    c = cfg("qwen1.5-0.5b")
    if case == "unknown":
        c["architectures"] = ["NoSuchForCausalLM"]
    elif case == "claimed-twice":
        monkeypatch.setattr(archs, "__path__", archs.__path__ + [str(TWIN_ROOT)])
    else:
        del c["architectures"]
    with pytest.raises(SystemExit, match="bench/configs/x.json.*claimed by"):
        archs.find(c, "bench/configs/x.json")


@pytest.mark.parametrize("m, k, n", [(48, 1024, 3072), (512, 5120, 13824), (1, 64, 32)])
def test_a_launch_of_g_tiles_costs_g_times_one_tile(m, k, n):
    least1, inten1, bound1 = work.launch_cost(1, m, k, n, V5E)
    least16, inten16, bound16 = work.launch_cost(16, m, k, n, V5E)
    assert least16 == pytest.approx(16 * least1, rel=1e-12)
    assert (inten16, bound16) == (pytest.approx(inten1), bound1)
    assert math.isfinite(least16)
