"""The yardstick's arithmetic: one hand-computed launch per configuration,
the peaks table, and the model work behind ``mfu``."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import archs, work  # noqa: E402

V5E = work.peaks("TPU v5 lite")


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def step_launches(c, m, head_rows):
    return archs.find(c).work.step_launches(c, m, head_rows)


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        work.peaks("TPU v99")


def test_qwen05b_decode_qkv_launch_is_bytes_bound():
    # attn.qkv at 64 slots: (M, K, N) = (64, 1024, 3 * 1024)
    site, g, m, k, n, count = step_launches(cfg("qwen1.5-0.5b"), 64, 64)[0]
    assert (site, g, m, k, n, count) == ("attn.qkv", 1, 64, 1024, 3072, 24)
    least, inten, bound = work.launch_cost(g, m, k, n, V5E)
    ops = 2 * 64 * 1024 * 3072                      # 402,653,184
    nbytes = 64 * 1024 + 1024 * 3072 + 4 * 64 * 3072  # 3,997,696
    assert bound == "bytes"
    assert least == pytest.approx(nbytes / 819e9)    # 4.881 us
    assert inten == pytest.approx(ops / nbytes)


def test_qwen14b_prefill_ffn_in_launch_is_ops_bound():
    c = cfg("qwen2.5-14b-1chip")
    launches = {s: (m, k, n, cnt) for s, _, m, k, n, cnt in step_launches(c, 512, 1)}
    assert launches["ffn.in"] == (512, 5120, 13824, 16)
    assert launches["attn.qkv"] == (512, 5120, 5120 + 2 * 1024, 8)
    assert launches["head"] == (1, 5120, 152064, 1)
    least, _, bound = work.launch_cost(1, 512, 5120, 13824, V5E)
    assert bound == "ops"
    assert least == pytest.approx(2 * 512 * 5120 * 13824 / 393e12)   # 184.4 us
    head_least, _, head_bound = work.launch_cost(1, 1, 5120, 152064, V5E)
    assert head_bound == "bytes"
    assert head_least == pytest.approx((5120 + 5120 * 152064 + 4 * 152064) / 819e9)


def test_least_kernel_seconds_sums_launches():
    c = cfg("qwen1.5-0.5b")
    one = sum(cnt * work.launch_cost(g, m, k, n, V5E)[0]
              for _, g, m, k, n, cnt in step_launches(c, 64, 64))
    assert work.least_kernel_seconds(c, V5E, 3, 64, 0, 256) == pytest.approx(3 * one)


def test_model_seconds_counts_each_kind_at_its_peak():
    c = cfg("qwen1.5-0.5b")
    d, f, layers, vocab = 1024, 2816, 24, 151936
    site = 2 * (d * 3 * d + d * d + 2 * d * f + f * d)
    want = (10 * site * layers / 393e12
            + (4 * 16 * 64 * layers * 55 + 2 * 2 * d * vocab) / 197e12)
    # ten positions 0..9 (sum of p + 1 = 55), two rows through the tied head
    assert work.model_seconds(c, V5E, 10, 2, [(0, 10)]) == pytest.approx(want)
    assert work.model_seconds(c, V5E, 10, 2, [(0, 4), (4, 10)]) == pytest.approx(want)


def test_kernel_launches_count_every_site_per_step():
    # qwen2.5-14b-1chip: 5 launches a layer x 8 layers, plus the analog head
    c = cfg("qwen2.5-14b-1chip")
    assert work.kernel_launches(c, 3, 8, 2, 512) == (3 + 2) * (5 * 8 + 1)
    # qwen1.5-0.5b: tied digital head, 5 x 24 a step
    assert work.kernel_launches(cfg("qwen1.5-0.5b"), 4, 64, 1, 256) == 5 * 120
